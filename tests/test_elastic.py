"""Elastic fleet supervisor (ISSUE 14): automatic failure detection →
mesh reshape → resume-at-new-world-size with zero operator action.

Unit level: exit-code classification, backoff schedule, restart-budget
exhaustion, the rejoin window restoring W, hung-worker heartbeat
detection, divergence-guard policy, generation stamping.  E2e: a
supervised 2-worker dist_sync fleet whose rank 1 is chaos-SIGKILLed
mid-run reshapes to W'=1, resumes from the newest verified checkpoint
and finishes with params matching the uninterrupted 2-worker control
at the PR-8 elastic tolerance — and ``merge_traces --health`` renders
the whole story as a restart timeline grouped by generation."""
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import chaos as chaos_mod
from mxnet_tpu import diagnostics as diag
from mxnet_tpu.elastic import (EXIT_RESTART_BUDGET, FleetSupervisor,
                               backoff_delay, classify_exit)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import launch  # noqa: E402  (tools/launch.py)

_ELASTIC_WORKER = os.path.join(os.path.dirname(__file__),
                               "elastic_worker.py")


def _child_env(extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("MXNET_CHAOS", None)
    env.update(extra or {})
    return env


# ---------------------------------------------------------------------
# tier-1 CLI: the no-jax state machine self-test
# ---------------------------------------------------------------------
def test_elastic_self_test_cli():
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.elastic", "--self-test"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT,
        timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["self_test_ok"], out


# ---------------------------------------------------------------------
# unit: classification + backoff schedule
# ---------------------------------------------------------------------
def test_classify_exit_table():
    assert classify_exit(0) == "ok"
    assert classify_exit(83) == "preempted"
    assert classify_exit(84) == "diverged"
    assert classify_exit(85) == "watchdog_abort"
    assert classify_exit(137) == classify_exit(-9) == "killed"
    assert classify_exit(-15) == "terminated"
    assert classify_exit(7) == "crashed"


def test_backoff_schedule():
    assert [backoff_delay(i, 0.5, jitter=False) for i in range(4)] == \
        [0.5, 1.0, 2.0, 4.0]
    for _ in range(8):
        v = backoff_delay(1, 0.5, jitter=True)
        assert 0.5 <= v <= 1.5


def _dummy_fleet(tmp_path, name, plan, n=2, **kw):
    """Exec-mode fleet of tiny python children whose exit code is
    keyed by (generation, rank) through the env plan."""
    body = ("import os,sys;"
            "g=int(os.environ['MXNET_ELASTIC_GENERATION']);"
            "r=int(os.environ['DMLC_WORKER_ID']);"
            "sys.exit(int(os.environ.get('ELASTIC_TEST_EXIT_G%d_R%d'"
            " % (g, r), '0')))")
    env = {"ELASTIC_TEST_EXIT_G%d_R%d" % k: str(v)
           for k, v in plan.items()}
    return FleetSupervisor(
        [sys.executable, "-c", body], num_workers=n, mode="exec",
        state_dir=str(tmp_path / name), backoff_s=0.01, jitter=False,
        monitor_interval_s=0.02, drain_s=2.0, env=env, **kw)


def test_restart_budget_exhaustion_exits_nonzero(tmp_path):
    sup = _dummy_fleet(tmp_path, "budget", {(g, 0): 1 for g in range(5)},
                       n=1, max_restarts=2)
    assert sup.run() == EXIT_RESTART_BUDGET
    assert sup.restarts == 3  # budget 2 spent + the exhausting attempt
    assert any(e["kind"] == "budget_exhausted" for e in sup.events)


def test_kill_reshapes_to_survivors(tmp_path):
    sup = _dummy_fleet(tmp_path, "reshape", {(0, 1): 137}, n=2,
                       max_restarts=3)
    assert sup.run() == 0
    worlds = [e["world_size"] for e in sup.events
              if e["kind"] == "launch"]
    assert worlds == [2, 1], sup.events
    # the events journal is on disk, content-classified for --health
    with open(sup.events_path) as f:
        payload = json.load(f)
    assert payload["elastic_supervisor"] is True


def test_rejoin_window_restores_w(tmp_path):
    import threading

    sup = _dummy_fleet(tmp_path, "rejoin", {(0, 1): 137}, n=2,
                       rejoin_s=10.0)

    def _touch_marker():
        time.sleep(0.3)
        with open(sup.slots.rejoin_path(1), "w"):
            pass

    t = threading.Thread(target=_touch_marker, daemon=True)
    t.start()
    assert sup.run() == 0
    t.join()
    worlds = [e["world_size"] for e in sup.events
              if e["kind"] == "launch"]
    assert worlds == [2, 2], sup.events
    assert any(e["kind"] == "slots_rejoined" and e["slots"] == [1]
               for e in sup.events)


def test_hung_worker_detected_and_killed(tmp_path):
    """A worker that stops heartbeating but never exits is declared
    hung, SIGKILLed and the fleet restarted — liveness is more than
    exit codes."""
    script = tmp_path / "hang.py"
    script.write_text(
        "import os, sys, time\n"
        "if int(os.environ['MXNET_ELASTIC_GENERATION']) > 0:\n"
        "    sys.exit(0)\n"
        "d = os.environ['MXNET_ELASTIC_HEARTBEAT_DIR']\n"
        "os.makedirs(d, exist_ok=True)\n"
        "open(os.path.join(d, 'hb_rank%s'\n"
        "     % os.environ['DMLC_WORKER_ID']), 'w').close()\n"
        "time.sleep(120)\n")
    sup = FleetSupervisor(
        [sys.executable, str(script)], num_workers=1, mode="exec",
        state_dir=str(tmp_path / "sup"), backoff_s=0.01, jitter=False,
        monitor_interval_s=0.05, drain_s=2.0,
        heartbeat_timeout_s=0.6, max_restarts=2)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 60
    assert any(e["kind"] == "worker_hung" for e in sup.events)
    assert any(e["kind"] == "fleet_down" and e["reason"] == "hung"
               for e in sup.events)


# ---------------------------------------------------------------------
# divergence guard: policy + wiring
# ---------------------------------------------------------------------
def test_divergence_guard_detection(monkeypatch):
    g = diag.DivergenceGuard(window=3, factor=2.0)
    assert not any(g.check(v) for v in (1.0, 1.1, 0.9, 1.2))
    assert g.check(10.0)          # spike vs window median
    assert g.check(float("nan"))  # non-finite always trips
    # disabled (window 0) never trips
    monkeypatch.delenv("MXNET_DIVERGENCE_WINDOW", raising=False)
    g0 = diag.DivergenceGuard()
    assert not g0.enabled and not g0.check(float("inf"))


def test_divergence_guard_raises_unsupervised(monkeypatch):
    monkeypatch.delenv("MXNET_ELASTIC_SUPERVISED", raising=False)
    g = diag.DivergenceGuard(window=2, factor=2.0)
    with pytest.raises(diag.DivergenceError):
        g.trip(step=5)


def test_divergence_exits_84_under_supervisor():
    code = (
        "import os\n"
        "os.environ['MXNET_ELASTIC_SUPERVISED'] = '1'\n"
        "from mxnet_tpu.diagnostics import DivergenceGuard\n"
        "g = DivergenceGuard(window=2, factor=2.0)\n"
        "assert not g.check(1.0) and not g.check(1.0)\n"
        "assert g.check(50.0, step=3)\n"
        "g.trip(3)\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env=_child_env(), timeout=300)
    assert res.returncode == diag.EXIT_DIVERGED, \
        (res.returncode, res.stdout, res.stderr)


def test_divergence_guard_wired_into_transformer_fit(monkeypatch):
    """The fit loop consults the guard every step — a trip stops
    training instead of continuing through garbage."""
    import jax

    from mxnet_tpu.transformer import (LMTokenIter, TransformerConfig,
                                       TransformerTrainStep)

    monkeypatch.setenv("MXNET_DIVERGENCE_WINDOW", "2")
    monkeypatch.delenv("MXNET_ELASTIC_SUPERVISED", raising=False)
    trips = []

    def fake_check(self, loss, step=None):
        trips.append(step)
        return step == 3

    monkeypatch.setattr(diag.DivergenceGuard, "check", fake_check)
    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=16,
                            n_heads=2, d_ff=32)
    s = TransformerTrainStep(cfg, seed=0)
    it = LMTokenIter(batch_size=2, seq_len=8, vocab_size=64,
                     num_sequences=16)
    with pytest.raises(diag.DivergenceError):
        s.fit(it, 6)
    assert trips == [1, 2, 3]


# ---------------------------------------------------------------------
# generation stamping: checkpoint + flight header
# ---------------------------------------------------------------------
def test_generation_stamped_everywhere(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_ELASTIC_GENERATION", "3")
    d = str(tmp_path / "ck")
    ckpt.CheckpointManager(d, rank=0, num_ranks=1,
                           async_write=False).save(
        2, params={"w": np.zeros(4, "f4")})
    payload = ckpt.load_checkpoint(d, rank=0, num_ranks=1)
    assert payload["generation"] == 3
    man = ckpt.read_manifest(d, 2)
    assert man["generation"] == 3
    header, _entries = diag.recorder.snapshot()
    assert header["generation"] == 3
    monkeypatch.delenv("MXNET_ELASTIC_GENERATION")
    header, _entries = diag.recorder.snapshot()
    assert header["generation"] == 0


# ---------------------------------------------------------------------
# the partial-epoch fast-forward invariant (satellite bugfix):
# scale_resume_skip and skip_batches agree on the GLOBAL sample
# position across world-size changes, including checkpoints taken
# where checkpoint_every_n does not divide the epoch
# ---------------------------------------------------------------------
def test_partial_epoch_skip_invariant_across_world_sizes(tmp_path):
    from mxnet_tpu.transformer import LMTokenIter, make_corpus

    corpus = make_corpus(64, 16, 64, seed=0)

    def _iter(world, rank, batch):
        return LMTokenIter(batch_size=batch, seq_len=16, vocab_size=64,
                           num_sequences=64, seed=0,
                           num_parts=world, part_index=rank)

    # W=2 fleet, per-rank batch 4, dies after 3 per-rank batches — a
    # MID-epoch position (8 batches/epoch; every_n=3 doesn't divide)
    d = str(tmp_path / "ck")
    for r in (0, 1):
        ckpt.CheckpointManager(d, rank=r, num_ranks=2,
                               async_write=False).save(
            3, params={"w": np.zeros(2, "f4")}, nbatch=3,
            iterator_state={"nbatch": 3, "batch_size": 4})
    # global position: 3 batches x 4 rows x 2 ranks = 24 rows consumed
    p = ckpt.load_checkpoint(d, rank=0, num_ranks=1)
    assert p["elastic"]["from_num_ranks"] == 2
    skip = ckpt.scale_resume_skip(p, 8)
    assert skip == 3  # 24 rows / (8 per batch x 1 rank)
    it1 = _iter(1, 0, 8)
    it1.reset()
    it1.skip_batches(skip)
    batch = it1.next()
    # the W'=1 iterator resumes at global row 24 — the row the W=2
    # fleet would have consumed next
    np.testing.assert_array_equal(batch.data[0].asnumpy()[0],
                                  corpus[24, :-1])
    # and the W=2 rank-0 iterator at the same logical position sees
    # the SAME global row (strided part: its row 12 is global row 24)
    it2 = _iter(2, 0, 4)
    it2.reset()
    it2.skip_batches(3)
    b2 = it2.next()
    np.testing.assert_array_equal(b2.data[0].asnumpy()[0],
                                  corpus[24, :-1])
    # wrap-around stays on the invariant too (skip past the epoch end)
    it3 = _iter(1, 0, 8)
    it3.reset()
    it3.skip_batches(10)  # 8/epoch: wraps into epoch 2, position 2
    b3 = it3.next()
    np.testing.assert_array_equal(b3.data[0].asnumpy()[0],
                                  corpus[16, :-1])


# ---------------------------------------------------------------------
# e2e acceptance: chaos-killed rank mid-run → supervisor reshapes 2→1
# and resumes from the newest verified checkpoint, no operator action;
# final params match the uninterrupted control at the PR-8 tolerance
# ---------------------------------------------------------------------
def test_supervisor_kill_reshape_resume_e2e(tmp_path, monkeypatch):
    # control: uninterrupted 2-worker cluster (same worker script)
    ctrl_prefix = str(tmp_path / "control")
    codes = launch.launch_local(
        2, 1, [sys.executable, _ELASTIC_WORKER, ctrl_prefix],
        env=_child_env({
            "MXNET_CKPT_DIR": str(tmp_path / "ck_ctrl"),
            "MXNET_CKPT_ASYNC": "0",
            "MXNET_DUMP_DIR": str(tmp_path / "dumps_ctrl"),
        }))
    assert codes == [0, 0], codes
    control = np.load(ctrl_prefix + "_rank0.npz")

    # supervised: chaos kills rank 1 the moment step 2's checkpoint is
    # resumable; the supervisor must do the whole recovery on its own
    ck = str(tmp_path / "ck")
    state_dir = str(tmp_path / "sup")
    dumps = str(tmp_path / "dumps")
    monkeypatch.setenv("MXNET_CHAOS", "kill_rank:rank=1,ckpt_step=2")
    chaos_mod.reset()
    out_prefix = str(tmp_path / "sup_out")
    sup = FleetSupervisor(
        [sys.executable, _ELASTIC_WORKER, out_prefix, "0.3"],
        num_workers=2, num_servers=1, mode="ps", state_dir=state_dir,
        ckpt_dir=ck, max_restarts=3, backoff_s=0.05, jitter=False,
        monitor_interval_s=0.05, drain_s=20.0,
        env=_child_env({
            "MXNET_CKPT_ASYNC": "0",
            "MXNET_PS_HEARTBEAT_INTERVAL": "0.2",
            "MXNET_KVSTORE_SYNC_TIMEOUT": "8",
            "MXNET_FLIGHT_RECORDER_DUMP": "1",
            "MXNET_DUMP_DIR": dumps,
        }))
    try:
        rc = sup.run()
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos_mod.reset()
    assert rc == 0, sup.events

    # the recovery really happened: chaos fired, the fleet died
    # "killed", and generation 1 launched at W'=1 resuming step >= 2
    kinds = [e["kind"] for e in sup.events]
    assert "chaos_kill" in kinds, sup.events
    assert any(e["kind"] == "fleet_down" and e["reason"] == "killed"
               for e in sup.events), sup.events
    launches = [e for e in sup.events if e["kind"] == "launch"]
    assert [e["world_size"] for e in launches] == [2, 1], launches
    assert launches[1]["resume_step"] >= 2, launches

    # zero operator action, same final params as the control (the
    # global batch sequence replays exactly; only summation order
    # differs at W'=1 — the PR-8 elastic tolerance)
    resumed = np.load(out_prefix + "_rank0.npz")
    assert sorted(control.files) == sorted(resumed.files)
    for k in control.files:
        np.testing.assert_allclose(
            resumed[k], control[k], rtol=2e-6, atol=1e-7,
            err_msg="supervised elastic resume diverged on %s" % k)

    # --health over BOTH generations' flight dumps + the supervisor
    # journal: the restart timeline names the kill and the reshape
    dump_files = sorted(glob.glob(os.path.join(
        dumps, "gen*", "flightrecorder_rank*.json")))
    assert dump_files, "no flight dumps under %s" % dumps
    tool = os.path.join(ROOT, "tools", "merge_traces.py")
    res = subprocess.run(
        [sys.executable, tool, "--health",
         os.path.join(state_dir, "supervisor_events.json")]
        + dump_files,
        capture_output=True, text=True, timeout=300)
    assert "RESTART TIMELINE: 2 generation(s)" in res.stdout, res.stdout
    assert "gen 0: W=2" in res.stdout, res.stdout
    assert "rank 1 killed (exit 137)" in res.stdout, res.stdout
    assert "gen 1: W=1, resumed from step" in res.stdout, res.stdout
    # the newest incarnation recovered healthy → exit 0
    assert res.returncode == 0, (res.returncode, res.stdout)
