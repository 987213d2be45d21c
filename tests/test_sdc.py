"""Silent-data-corruption defense (ISSUE 15): cross-rank fingerprint
voting, supervisor quarantine, and the offline replay audit.

Unit level: fingerprint/vote semantics ride the tier-1 CLI self-test;
here the python-level surfaces — the in-graph detector on a CPU dp
mesh (a per-device flipped bit is named by device index), the exit-87
contract under supervision, the conv-path divergence-guard wiring, and
the replay audit catching a poisoned-but-sha256-verified checkpoint
chain.  E2e: a supervised 2-worker dist_sync fleet whose rank 1
suffers a chaos ``bitflip_param`` is named by the vote (rank + step +
bucket in the flight dump's ``sdc`` event), exits 87, is QUARANTINED
(no rejoin), and the fleet reshapes 2→1 and resumes from the newest
verified checkpoint with final params matching the uninterrupted
control at the PR-8 tolerance — zero operator action."""
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos as chaos_mod
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import diagnostics as diag
from mxnet_tpu import sdc
from mxnet_tpu.elastic import FleetSupervisor

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
    env.pop("MXNET_SDC_CHECK_EVERY_N", None)
    env.update(extra or {})
    return env


# ---------------------------------------------------------------------
# tier-1 CLI: the no-jax detector units
# ---------------------------------------------------------------------
def test_sdc_self_test_cli():
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.sdc", "--self-test"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT,
        timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["self_test_ok"], out


# ---------------------------------------------------------------------
# unit: fingerprints + vote (the python surfaces the CLI rides)
# ---------------------------------------------------------------------
def test_fingerprint_bitflip_and_vote():
    a = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    fp0 = sdc.fingerprint_np(a)
    b = chaos_mod.flip_bit_np(a.copy(), 77).reshape(a.shape)
    assert sdc.fingerprint_np(b) != fp0
    # W=3 names the minority and its bucket; W=2 needs the reference
    good, bad = [fp0, 7], [sdc.fingerprint_np(b), 7]
    v = sdc.vote({0: good, 1: good, 2: bad})
    assert v["conclusive"] and v["minority"] == [2]
    assert v["mismatched_buckets"][2]["buckets"] == [0]
    v2 = sdc.vote({0: good, 1: bad})
    assert not v2["conclusive"]
    v3 = sdc.vote({0: good, 1: bad}, reference=good)
    assert v3["conclusive"] and v3["minority"] == [1]


def test_guard_trip_exits_87_under_supervisor():
    code = (
        "import os\n"
        "os.environ['MXNET_ELASTIC_SUPERVISED'] = '1'\n"
        "from mxnet_tpu import sdc\n"
        "g = sdc.SDCGuard(every_n=1)\n"
        "g.apply({0: [1, 2], 1: [1, 9]}, step=4, my_rank=1,\n"
        "        reference_fn=lambda: [1, 2])\n"
        "raise SystemExit('unreachable: apply must os._exit(87)')\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env=_child_env(), timeout=300)
    assert res.returncode == sdc.EXIT_SDC, \
        (res.returncode, res.stdout, res.stderr)


# ---------------------------------------------------------------------
# the in-graph detector: a per-device flipped bit on a CPU dp mesh is
# caught by the gathered fingerprint rows and NAMED by device index
# ---------------------------------------------------------------------
def _corrupt_one_device(mesh, arr, device_index, bit):
    """A 'replicated' (P()) array whose ``device_index`` replica holds
    a flipped bit — exactly what a corrupt chip's HBM would hold."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    host = np.asarray(arr)
    bufs = []
    for i, d in enumerate(mesh.devices.flat):
        h = host if i != device_index else \
            chaos_mod.flip_bit_np(host.copy(), bit).reshape(host.shape)
        bufs.append(jax.device_put(h, d))
    return jax.make_array_from_single_device_arrays(
        host.shape, NamedSharding(mesh, P()), bufs)


def test_transformer_mesh_detector_names_device(monkeypatch):
    import jax

    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.transformer import (LMTokenIter, TransformerConfig,
                                       TransformerTrainStep)

    monkeypatch.setenv("MXNET_SDC_CHECK_EVERY_N", "1")
    monkeypatch.delenv("MXNET_ELASTIC_SUPERVISED", raising=False)
    mesh = make_mesh((3,), ("dp",), jax.devices()[:3])
    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=16,
                            n_heads=2, d_ff=32)
    s = TransformerTrainStep(cfg, mesh=mesh, seed=0)
    it = LMTokenIter(batch_size=6, seq_len=8, vocab_size=64,
                     num_sequences=24)
    b = it.next()
    s.step(b.data[0], b.label[0])
    rows = np.asarray(s.sdc_rows(s._sdc_ctr))
    assert rows.shape[0] == 3 and rows.any()
    assert np.array_equal(rows[0], rows[1]) \
        and np.array_equal(rows[0], rows[2])
    guard = sdc.SDCGuard(every_n=1)
    assert guard.check_rows(rows, step=1)["ok"]

    # flip one bit on device 2's replica only: the next step's rows
    # disagree and the W=3 vote names device 2 (and its bucket)
    name = sorted(s._params)[0]
    s._params[name] = _corrupt_one_device(mesh, s._params[name], 2, 12)
    s.step(b.data[0], b.label[0])
    rows = np.asarray(s.sdc_rows(s._sdc_ctr))
    assert not np.array_equal(rows[0], rows[2])
    with pytest.raises(sdc.SDCError) as ei:
        guard.check_rows(rows, step=2)
    assert "(2) at step 2" in str(ei.value)  # device 2 named
    assert "bucket(s) [0]" in str(ei.value)
    # the flight-recorder 'sdc' event carries (rank, step, bucket,
    # expected-vs-got) — the post-mortem evidence the dump persists
    _hdr, entries = diag.recorder.snapshot()
    ev = [e for e in entries if e["op"] == "sdc"]
    assert ev, "no sdc flight event recorded"
    args = ev[-1]["args"]
    assert args["step"] == 2 and args["minority_rank"] == 2
    assert args["buckets"] and args["detail"]


def test_fused_step_sdc_rows(monkeypatch):
    import jax

    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("MXNET_SDC_CHECK_EVERY_N", "2")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    fts = FusedTrainStep(net, SoftmaxCrossEntropyLoss(), mesh=mesh)
    X = mx.nd.array(np.random.RandomState(0).randn(8, 6)
                    .astype("float32"))
    y = mx.nd.array((np.arange(8) % 4).astype("float32"))
    for _ in range(4):
        fts(X, y)
    assert fts.bucketed and fts._sdc
    rows = np.asarray(fts._last_sdc_rows)
    assert rows.shape[0] == 2 and rows.any()
    assert np.array_equal(rows[0], rows[1])
    # cadence: step 3 (odd) computes zeros under the cond — the
    # param-bytes pass is only paid every MXNET_SDC_CHECK_EVERY_N
    fts(X, y)
    assert not np.asarray(fts._last_sdc_rows).any()


def test_sdc_off_by_default_unchanged_step(monkeypatch):
    """MXNET_SDC_CHECK_EVERY_N unset: the step builds without the
    fingerprint output — the off path is the exact pre-SDC graph."""
    import jax

    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    monkeypatch.delenv("MXNET_SDC_CHECK_EVERY_N", raising=False)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    fts = FusedTrainStep(net, SoftmaxCrossEntropyLoss(), mesh=mesh)
    X = mx.nd.array(np.random.RandomState(1).randn(8, 6)
                    .astype("float32"))
    y = mx.nd.array((np.arange(8) % 4).astype("float32"))
    loss, logits = fts(X, y)
    assert not fts._sdc and fts._last_sdc_rows is None
    assert np.isfinite(float(loss.asnumpy().mean()))


# ---------------------------------------------------------------------
# satellite: the conv-path divergence guard (transformer parity)
# ---------------------------------------------------------------------
def _tiny_module():
    from mxnet_tpu import sym

    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=8)
    net = sym.SoftmaxOutput(data=net, name="softmax")
    return mx.mod.Module(symbol=net, context=mx.cpu())


def test_divergence_guard_wired_into_module_fit(monkeypatch):
    monkeypatch.setenv("MXNET_DIVERGENCE_WINDOW", "2")
    monkeypatch.delenv("MXNET_ELASTIC_SUPERVISED", raising=False)
    steps = []

    def fake_check(self, loss, step=None):
        steps.append(step)
        return step == 3

    monkeypatch.setattr(diag.DivergenceGuard, "check", fake_check)
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    y = (np.arange(16) % 4).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=4)
    mod = _tiny_module()
    with pytest.raises(diag.DivergenceError):
        mod.fit(it, num_epoch=2, optimizer="sgd", kvstore="local",
                eval_metric="ce")
    assert steps == [1, 2, 3]


def test_divergence_guard_sees_per_step_loss_not_running_mean(
        monkeypatch):
    """The conv-path guard recovers the PER-STEP loss from the
    metric's (sum, count) deltas: a 7x spike on batch 20 of an epoch
    trips, where the epoch-running mean (~(19·2+14)/20 ≈ 2.5, under
    the 3x-median threshold) would have diluted it into invisibility."""
    monkeypatch.setenv("MXNET_DIVERGENCE_WINDOW", "4")
    monkeypatch.setenv("MXNET_DIVERGENCE_FACTOR", "3.0")
    monkeypatch.delenv("MXNET_ELASTIC_SUPERVISED", raising=False)
    seen = []
    orig = diag.DivergenceGuard.check

    def spy(self, loss, step=None):
        seen.append((step, float(loss)))
        return orig(self, loss, step=step)

    monkeypatch.setattr(diag.DivergenceGuard, "check", spy)
    rng = np.random.RandomState(0)
    x = rng.randn(80, 4).astype(np.float32) * 0.01
    y = np.zeros(80, dtype=np.float32)
    x[76:] = np.abs(x[76:]) * 1e7  # batch 20 is garbage
    y[76:] = 3
    it = mx.io.NDArrayIter(x, y, batch_size=4, shuffle=False)
    mod = _tiny_module()
    with pytest.raises(diag.DivergenceError):
        mod.fit(it, num_epoch=1, optimizer="sgd", kvstore="local",
                eval_metric="ce")
    step, spike = seen[-1]
    assert step == 20 and spike > 7.0, seen[-3:]
    # the 19 clean steps fed ~flat per-batch values, not a drifting
    # cumulative mean polluted by the spike
    prior = [v for _s, v in seen[:-1]]
    assert max(prior) < 2.5, prior


def test_loss_signal_picks_loss_like_metric():
    assert diag.loss_signal([("accuracy", 0.9),
                             ("cross-entropy", 1.7)]) == 1.7
    assert diag.loss_signal([("accuracy", 0.9)]) is None
    # a non-finite metric is garbage whatever its name
    assert diag.loss_signal([("accuracy", float("nan"))]) != \
        diag.loss_signal([("accuracy", 0.9)])


def test_bitflip_grad_injected_in_module_fit(monkeypatch):
    """bitflip_grad fires in the mid-step window and training carries
    on — the uniform-corruption case only the replay audit can catch
    (there is no cross-rank disagreement to vote on)."""
    monkeypatch.setenv("MXNET_CHAOS", "bitflip_grad:rank=0,step=2")
    chaos_mod.reset()
    try:
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        y = (np.arange(16) % 4).astype(np.float32)
        it = mx.io.NDArrayIter(x, y, batch_size=4)
        mod = _tiny_module()
        mod.fit(it, num_epoch=1, optimizer="sgd", kvstore="local")
        assert chaos_mod.injected_total("bitflip_grad") == 1
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos_mod.reset()


# ---------------------------------------------------------------------
# replay audit: the offline corruption bisector
# ---------------------------------------------------------------------
def test_replay_audit_clean_and_poisoned(tmp_path, monkeypatch):
    from mxnet_tpu.transformer import (LMTokenIter, TransformerConfig,
                                       TransformerTrainStep)

    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=16,
                            n_heads=2, d_ff=32)

    def run(d, chaos=None):
        if chaos:
            monkeypatch.setenv("MXNET_CHAOS", chaos)
        else:
            monkeypatch.delenv("MXNET_CHAOS", raising=False)
        chaos_mod.reset()
        try:
            s = TransformerTrainStep(cfg, seed=0)
            it = LMTokenIter(batch_size=4, seq_len=8, vocab_size=64,
                             num_sequences=16)
            s.fit(it, 6, checkpoint_every_n=2, checkpoint_dir=str(d))
        finally:
            monkeypatch.delenv("MXNET_CHAOS", raising=False)
            chaos_mod.reset()

    # clean run: every interval reproduces its successor bitwise
    clean = tmp_path / "clean"
    run(clean)
    rep = sdc.replay_audit(str(clean), step=2)
    assert rep["match"] and rep["steps_replayed"] == 2, rep
    # the next MANIFEST carries the per-param fingerprints the audit
    # compares against (shard-independent comparison target)
    assert rep["manifest_fps"] == {"present": True, "match": True,
                                   "mismatched_keys": []}, rep
    man = ckpt.read_manifest(str(clean), 4)
    assert man["shards"]["0"]["param_fps"], man
    assert sdc.replay_bisect(str(clean))["ok"]

    # poisoned run: a W=1 bitflip at step 3 that the VOTE cannot see
    # and sha256 verifies (the bytes on disk ARE the bytes written) —
    # the replay audit bisects the corruption to the (2, 4) interval
    bad = tmp_path / "bad"
    run(bad, chaos="bitflip_param:rank=0,step=3")
    assert ckpt.verify_dir(str(bad))["ok"], \
        "sha256 must PASS — the corruption is pre-write"
    rep = sdc.replay_bisect(str(bad))
    assert not rep["ok"] and rep["first_corrupt_interval"] == (2, 4), rep

    # the CLI exits 3 on the mismatch, 0 on the clean chain
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.sdc", "--replay", str(bad),
         "--json"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT,
        timeout=600)
    assert res.returncode == 3, (res.returncode, res.stdout,
                                 res.stderr)
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["first_corrupt_interval"] == [2, 4], out


# ---------------------------------------------------------------------
# e2e acceptance: supervised 2-worker fleet + bitflip on rank 1 →
# the vote names rank 1 (flight 'sdc' event with step + bucket), rank
# exits 87, the supervisor QUARANTINES the slot (rejoin marker
# ignored), reshapes 2→1, resumes from the newest verified checkpoint,
# and the final params match the uninterrupted control — zero operator
# action; --health renders the quarantine in the restart timeline
# ---------------------------------------------------------------------
def test_sdc_quarantine_reshape_resume_e2e(tmp_path, monkeypatch):
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

    ck = str(tmp_path / "ck")
    state_dir = str(tmp_path / "sup")
    dumps = str(tmp_path / "dumps")
    monkeypatch.setenv("MXNET_CHAOS", "bitflip_param:rank=1,step=3")
    chaos_mod.reset()
    out_prefix = str(tmp_path / "sup_out")
    sup = FleetSupervisor(
        [sys.executable, _ELASTIC_WORKER, out_prefix, "0.2"],
        num_workers=2, num_servers=1, mode="ps", state_dir=state_dir,
        ckpt_dir=ck, max_restarts=3, backoff_s=0.05, rejoin_s=1.0,
        jitter=False, monitor_interval_s=0.05, drain_s=20.0,
        env=_child_env({
            "MXNET_CKPT_ASYNC": "0",
            "MXNET_SDC_CHECK_EVERY_N": "1",
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

    # the detector fired: the corrupt worker exited 87 and its SLOT
    # was quarantined (the kvstore registration race decides which
    # spawn slot carries kv rank 1, so the slot index is whichever
    # machine the corrupt rank ran on), and gen 1 launched at W'=1
    # resuming a verified step
    sdc_exits = [e for e in sup.events if e["kind"] == "worker_exit"
                 and e["exit_code"] == sdc.EXIT_SDC]
    assert len(sdc_exits) == 1, sup.events
    bad_slot = sdc_exits[0]["slot"]
    assert any(e["kind"] == "fleet_down" and e["reason"] == "sdc"
               for e in sup.events), sup.events
    assert any(e["kind"] == "slot_quarantined"
               and e["slot"] == bad_slot
               for e in sup.events), sup.events
    assert not any(e["kind"] == "slots_rejoined"
                   for e in sup.events), sup.events
    launches = [e for e in sup.events if e["kind"] == "launch"]
    assert [e["world_size"] for e in launches] == [2, 1], launches
    assert launches[1]["resume_step"] >= 2, launches
    assert sup.slots.quarantined() == [bad_slot]

    # the corrupt rank's flight dump carries the 'sdc' event naming
    # (rank, step, bucket, expected-vs-got)
    dump_path = os.path.join(dumps, "gen0",
                             "flightrecorder_rank1.json")
    assert os.path.exists(dump_path), os.listdir(
        os.path.join(dumps, "gen0"))
    with open(dump_path) as f:
        payload = json.load(f)
    assert payload["header"]["reason"] == "sdc", payload["header"]
    ev = [e for e in payload["entries"] if e["op"] == "sdc"]
    assert ev, "no sdc event in the flight dump"
    args = ev[-1]["args"]
    assert args["minority_rank"] == 1 and args["self_rank"] == 1
    assert args["step"] == 3, args
    assert args["buckets"], args
    assert args["detail"], args

    # zero operator action, same final params as the control (the
    # global batch sequence replays exactly at W'=1 — the PR-8
    # elastic tolerance; the flipped bit never reached rank 0 or a
    # checkpoint shard)
    resumed = np.load(out_prefix + "_rank0.npz")
    assert sorted(control.files) == sorted(resumed.files)
    for k in control.files:
        np.testing.assert_allclose(
            resumed[k], control[k], rtol=2e-6, atol=1e-7,
            err_msg="post-quarantine elastic resume diverged on %s" % k)

    # --health over both generations + the journal: the restart
    # timeline names the quarantine; the recovered fleet exits 0
    dump_files = sorted(glob.glob(os.path.join(
        dumps, "gen*", "flightrecorder_rank*.json")))
    assert dump_files
    tool = os.path.join(ROOT, "tools", "merge_traces.py")
    res = subprocess.run(
        [sys.executable, tool, "--health",
         os.path.join(state_dir, "supervisor_events.json")]
        + dump_files,
        capture_output=True, text=True, timeout=300)
    assert "RESTART TIMELINE: 2 generation(s)" in res.stdout, res.stdout
    assert "slot %d QUARANTINED (sdc)" % bad_slot in res.stdout, \
        res.stdout
    assert "gen 1: W=1, resumed from step" in res.stdout, res.stdout
    assert res.returncode == 0, (res.returncode, res.stdout)
