"""The bench's driver contract (VERDICT r3 weak #1): the final JSON line
must survive an external timeout.  Round 3 lost its io/fit evidence to a
SIGTERM with nothing emitted; these tests pin the cumulative-emit
machinery without running any model (signal handler + fallback headline
logic are pure Python).
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, timeout=60):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=HERE,
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


def test_sigterm_emits_cumulative_json():
    code = """
import json, os, signal
import bench
bench._STATE["kind"] = "TPU v5 lite"
bench._STATE["peak"] = 197e12
bench._STATE["table"].append({
    "model": "resnet50_v1", "batch": 32, "dtype": "float32",
    "images_per_sec_per_chip": 1300.0, "vs_k80_baseline": 11.9})
bench._STATE["headline"] = 1300.0
bench._STATE["io"] = {"pipeline": "ImageRecordIter->train",
                      "decode_ips_1core": 1000.0}
bench._install_signal_emit()
os.kill(os.getpid(), signal.SIGTERM)
raise SystemExit("handler did not fire")
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-500:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"] == "resnet50_train_images_per_sec"
    assert out["value"] == 1300.0
    assert out["table"][0]["model"] == "resnet50_v1"
    assert out["io"]["decode_ips_1core"] == 1000.0
    assert "truncated" in out  # honest marker: the run was cut short


def test_headline_fallback_and_single_emit():
    """headline=None falls back to a resnet50 row; double emit is
    suppressed (signal during final print must not duplicate)."""
    code = """
import json
import bench
bench._STATE["table"].append({"model": "resnet18_v1",
                              "images_per_sec_per_chip": 3000.0})
bench._STATE["table"].append({"model": "resnet50_v1",
                              "images_per_sec_per_chip": 1200.0})
bench._emit_final()
bench._emit_final()  # no-op
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-500:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1
    out = json.loads(lines[0])
    # only a resnet50 row may stand in for the headline — never resnet18
    assert out["value"] == 1200.0
    assert out["vs_baseline"] == round(1200.0 / 109.0, 2)


def test_final_json_stamps_autotune_and_compression():
    """ISSUE 12 satellite: the final JSON carries the self-tuning-
    collectives block — tuned-plan provenance (null when untuned) and
    the 2-bit wire accounting (uncompressed vs compressed push bytes,
    the real 16x encode verified inline) next to the bucketing block."""
    code = """
import bench
bench._STATE["table"].append({"model": "resnet50_v1",
                              "images_per_sec_per_chip": 1200.0})
bench._emit_final()
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    at = out["autotune"]
    assert "tuned_plan" in at and "plan_env" in at
    comp = at["compression"]
    assert comp["type"] == "2bit"
    assert comp["push_bytes_uncompressed"] > comp["push_bytes_compressed"]
    assert comp["wire_ratio"] == 16.0
    assert "mxnet_kvstore_bytes_total_push" in comp
    assert "bucketing" in out


def test_final_json_stamps_sdc_overhead():
    """ISSUE 15 acceptance: the final JSON carries the sdc block —
    checks run, measured per-check seconds over the benched gradient
    footprint, fraction of step time, and the zero-cost-when-off
    contract (off by default)."""
    code = """
import bench
bench._STATE["table"].append({"model": "resnet50_v1", "batch": 32,
                              "images_per_sec_per_chip": 1200.0})
bench._emit_final()
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    s = out["sdc"]
    assert s["enabled"] is False and s["check_every_n"] == 0
    assert s["checks_run"] == 0
    assert s["per_check_seconds"] > 0
    assert s["fingerprint_bytes"] > 0
    # a real wall-clock measurement against a synthetic 26.7ms step:
    # assert sign/presence, not magnitude (a loaded CI box must not
    # flake this)
    assert s["fraction_of_step_time"] > 0
    assert s["amortized_fraction_of_step_time"] == 0.0
    assert s["hot_path_cost_when_off_seconds"] == 0.0


def test_headline_zero_when_no_resnet50():
    code = """
import bench
bench._STATE["table"].append({"model": "alexnet",
                              "images_per_sec_per_chip": 9000.0})
bench._emit_final()
"""
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    assert out["value"] == 0.0  # an honest failure, not a wrong model


def test_watchdog_exits_rc0_while_main_thread_blocked():
    """Round-5 contract: the watchdog thread bounds TOTAL wall clock,
    emitting the cumulative JSON and exiting rc=0 even while the main
    thread is stuck in a blocking call (r4's failure mode: phase gates
    guard entry only, so one slow compile overran the driver window)."""
    code = """
import time
import bench
bench._STATE["table"].append({"model": "resnet50_v1",
                              "images_per_sec_per_chip": 1111.0})
bench._install_watchdog(1.0)
time.sleep(60)  # stand-in for a compile the main thread can't escape
"""
    proc = _run(code, timeout=30)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    assert out["value"] == 1111.0
    assert "deadline" in out["truncated"]


def test_phase_order_fit_and_memory_before_io_and_bare():
    """The rows the driver has never captured (fit, memory) must run
    before the rows it has (io, bare, sweep) — pinned at source level so
    a refactor can't silently demote them again."""
    src = open(os.path.join(HERE, "bench.py")).read()
    i_fit = src.index("phase 2: Module.fit")
    i_mem = src.index("phase 3: remat memory")
    i_io = src.index("phase 4: decomposed IO")
    i_bare = src.index("phase 5: bare-JAX")
    assert i_fit < i_mem < i_io < i_bare


def test_deadline_leaves_emit_margin():
    src = open(os.path.join(HERE, "bench.py")).read()
    import re

    m = re.search(r"_EMIT_MARGIN_S\s*=\s*(\d+(?:\.\d+)?)", src)
    assert m and float(m.group(1)) >= 120.0


def test_round6_budget_and_emission_order():
    """Round-6 contract: default budget <= 1000 s (self-deadline fires
    inside a 1200 s external window) and the emission order is one bf16
    headline row -> fit probe at the cheapest rung -> memory -> fp32."""
    import re

    src = open(os.path.join(HERE, "bench.py")).read()
    m = re.search(r'BENCH_BUDGET_S\s*=\s*float\(os\.environ\.get\('
                  r'"BENCH_BUDGET_S",\s*"(\d+(?:\.\d+)?)"\)\)', src)
    assert m and float(m.group(1)) <= 1000.0
    i1 = src.index("phase 1: ONE bf16 headline")
    i2 = src.index("phase 2: Module.fit probe")
    i3 = src.index("phase 3: remat memory")
    i3b = src.index("phase 3b: fp32 headline")
    assert i1 < i2 < i3 < i3b
    # the bf16 row is the only phase-1 headline row
    hm = re.search(r"HEADLINE_CONFIGS = \[\n(.*?)\]", src, re.S)
    assert hm and "bfloat16" in hm.group(1) and \
        "float32" not in hm.group(1)


def test_budget_default_inside_driver_window():
    """r3 regression: the 4200 s default demonstrably exceeded the
    driver's timeout.  Pin the SOURCE default (not any env override the
    running shell happens to carry) so a future edit can't silently
    regress the driver contract."""
    import re

    src = open(os.path.join(HERE, "bench.py")).read()
    m = re.search(r'BENCH_BUDGET_S\s*=\s*float\(os\.environ\.get\('
                  r'"BENCH_BUDGET_S",\s*"(\d+(?:\.\d+)?)"\)\)', src)
    assert m, "BENCH_BUDGET_S default not found in bench.py"
    assert float(m.group(1)) <= 2400.0
