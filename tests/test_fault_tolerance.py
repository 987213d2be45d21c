"""Fault-tolerance tests: elastic checkpoint/resume, preemption +
watchdog recovery, kvstore retry/backoff, and the chaos harness that
proves recovery end-to-end.

The reference's fault story lived in ps-lite (is_recovery rejoin,
kvstore_dist.h:54-58) and was tested by hand-driven nightly scripts;
here the chaos harness (mxnet_tpu/chaos.py) injects the faults inside
the runtime — a dropped push response, a SIGKILL'd worker mid-step, a
NaN gradient, a permanent collective hang — and these tests assert the
system RECOVERS: bitwise-exact resume, retry-absorbed drops, documented
exit codes, dead peers named by merge_traces --health."""
import contextlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import sym

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import launch  # noqa: E402  (tools/launch.py)

_FT_WORKER = os.path.join(os.path.dirname(__file__), "ft_worker.py")
_DIST_WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")


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
# chaos harness
# ---------------------------------------------------------------------
def test_chaos_self_test():
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.chaos", "--self-test"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.splitlines()[-1])
    assert payload["self_test_ok"], payload


def test_chaos_spec_parsing_inert_without_env(monkeypatch):
    from mxnet_tpu import chaos

    monkeypatch.delenv("MXNET_CHAOS", raising=False)
    chaos.reset()
    assert not chaos.enabled()
    assert chaos.fault("kill", step=1) is None
    monkeypatch.setenv("MXNET_CHAOS", "delay_collective:op=push,ms=1")
    chaos.reset()
    assert chaos.enabled()
    t0 = time.time()
    chaos.maybe_delay("push")
    assert time.time() - t0 < 0.5  # 1ms sleep, not the 200ms default
    assert chaos.injected_total("delay_collective") == 1
    chaos.reset()


# ---------------------------------------------------------------------
# checkpoint layer (tier-1 roundtrip per the CI satellite)
# ---------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = ckpt.CheckpointManager(d, keep=2, async_write=False,
                                 rank=0, num_ranks=1)
    params = {"w": np.arange(6).reshape(2, 3).astype("f4")}
    p = mgr.save(2, params=params, optimizer_states=b"momenta",
                 epoch=0, nbatch=2)
    assert os.path.exists(p) and not os.path.exists(p + ".tmp")
    loaded = mgr.load()
    assert loaded["format_version"] == ckpt.FORMAT_VERSION
    assert loaded["step"] == 2 and loaded["nbatch"] == 2
    assert loaded["optimizer_states"] == b"momenta"
    np.testing.assert_array_equal(loaded["params"]["w"], params["w"])
    assert loaded["rng"]["root_key"] is not None  # conftest seeded

    # retention: keep=2 of steps {2,4,6} drops step 2
    mgr.save(4, params=params)
    mgr.save(6, params=params)
    assert ckpt.list_steps(d) == [4, 6]
    assert mgr.latest_step() == 6

    # versioning: a shard from the future is refused, not misread
    import pickle

    bad = ckpt.shard_path(d, 8, 0)
    os.makedirs(os.path.dirname(bad), exist_ok=True)
    with open(bad, "wb") as f:
        pickle.dump({"format_version": ckpt.FORMAT_VERSION + 1}, f)
    with pytest.raises(ValueError, match="format_version"):
        ckpt.load_checkpoint(d, step=8, rank=0)


def test_checkpoint_completeness_is_per_fleet(tmp_path):
    """A step counts as resumable only when EVERY rank's shard landed —
    the elastic contract for a fleet that died unevenly."""
    d = str(tmp_path)
    m0 = ckpt.CheckpointManager(d, async_write=False, rank=0, num_ranks=2)
    m1 = ckpt.CheckpointManager(d, async_write=False, rank=1, num_ranks=2)
    m0.save(2, params={})
    m1.save(2, params={})
    m0.save(4, params={})  # rank 1 died before its step-4 shard
    assert ckpt.latest_step(d, num_ranks=2) == 2
    assert ckpt.latest_step(d, num_ranks=1) == 4  # single-rank view
    with pytest.raises(FileNotFoundError):
        ckpt.load_checkpoint(str(tmp_path / "empty"), rank=0, num_ranks=1)


def test_checkpoint_async_writer(tmp_path):
    d = str(tmp_path)
    mgr = ckpt.CheckpointManager(d, async_write=True, rank=0, num_ranks=1)
    params = {"w": np.zeros((128, 128), "f4")}
    mgr.save(1, params=params, blocking=False)
    assert mgr.wait(timeout=30)
    assert mgr.latest_step() == 1
    # the snapshot was taken at save() time: mutating after must not leak
    params["w"][:] = 7
    np.testing.assert_array_equal(mgr.load()["params"]["w"], 0)


def test_load_checkpoint_names_missing_ranks(tmp_path):
    """The serving satellite: a failed load must say exactly WHICH
    ranks' shards are missing, not just 'file not found' — server
    startup has to explain why a model won't load."""
    d = str(tmp_path / "partial")
    m0 = ckpt.CheckpointManager(d, async_write=False, rank=0,
                                num_ranks=4)
    m2 = ckpt.CheckpointManager(d, async_write=False, rank=2,
                                num_ranks=4)
    m0.save(9, params={})
    m2.save(9, params={})  # ranks 1 and 3 died before writing
    # newest-complete path: no step is complete, error names the gaps
    with pytest.raises(FileNotFoundError) as ei:
        ckpt.load_checkpoint(d, num_ranks=4, rank=0)
    msg = str(ei.value)
    assert "rank(s) [1, 3]" in msg and "of 4" in msg, msg
    assert "present: [0, 2]" in msg, msg
    # explicit-step path: same naming when the requested shard is gone
    with pytest.raises(FileNotFoundError) as ei:
        ckpt.load_checkpoint(d, step=9, rank=3, num_ranks=4)
    msg = str(ei.value)
    assert "step 9" in msg and "rank(s) [1, 3]" in msg, msg
    assert ckpt.missing_ranks(d, 9, 4) == [1, 3]
    # an empty directory reports that there is nothing at all
    with pytest.raises(FileNotFoundError, match="no step_"):
        ckpt.load_checkpoint(str(tmp_path / "void"), rank=0,
                             num_ranks=1)


def test_ckpt_write_retries_when_janitor_removes_dir(tmp_path,
                                                     monkeypatch):
    """Deterministic half of the GC-vs-writer race satellite: the
    janitor rmdir's a step between the writer's makedirs and its
    os.replace — the write must retry once and land the shard instead
    of surfacing a spurious writer error."""
    import shutil

    d = str(tmp_path / "retry")
    mgr = ckpt.CheckpointManager(d, keep=0, async_write=False, rank=0,
                                 num_ranks=1)
    real_replace = os.replace
    struck = {"n": 0}

    def janitor_strikes_once(src, dst):
        if dst.endswith("rank0.ckpt") and struck["n"] == 0:
            struck["n"] = 1
            shutil.rmtree(os.path.dirname(dst))
            raise FileNotFoundError(dst)
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", janitor_strikes_once)
    mgr.save(3, params={"w": np.ones(4, "f4")}, blocking=True)
    monkeypatch.undo()
    assert struck["n"] == 1  # the race actually fired
    assert ckpt.latest_step(d, num_ranks=1) == 3
    loaded = ckpt.load_checkpoint(d, step=3, rank=0, num_ranks=1)
    np.testing.assert_array_equal(loaded["params"]["w"], 1)


def test_ckpt_gc_janitor_vs_async_writer_stress(tmp_path):
    """Stress half of the race satellite: rank 0's retention janitor
    (keep=1) GCs steps WHILE both ranks' async writers stream shards
    and a reader polls.  Invariants: latest_step never names a step a
    reader can't load (unless GC legitimately advanced past it), no
    torn/corrupt shard is ever read, and the writers surface no
    errors."""
    d = str(tmp_path / "race")
    m0 = ckpt.CheckpointManager(d, keep=1, async_write=True, rank=0,
                                num_ranks=2)
    m1 = ckpt.CheckpointManager(d, keep=1, async_write=True, rank=1,
                                num_ranks=2)
    params = {"w": np.arange(256, dtype="f4")}
    stop = threading.Event()
    problems = []

    def reader():
        while not stop.is_set():
            s = ckpt.latest_step(d, num_ranks=2)
            if s is None:
                time.sleep(0.001)
                continue
            try:
                for r in (0, 1):
                    payload = ckpt.load_checkpoint(d, step=s, rank=r,
                                                   num_ranks=2)
                    if payload["step"] != s:
                        problems.append("step %d shard says %r"
                                        % (s, payload["step"]))
            except FileNotFoundError:
                # only legitimate when the janitor moved PAST s: a
                # half-deleted dir still reported by latest_step is
                # exactly the bug this test exists to catch
                s2 = ckpt.latest_step(d, num_ranks=2)
                if s2 is None or s2 <= s:
                    problems.append(
                        "latest_step says %r but step %d unloadable"
                        % (s2, s))
            except Exception as e:  # torn pickle etc.
                problems.append(repr(e))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        for step in range(1, 26):
            m1.save(step, params=params, blocking=False)
            m0.save(step, params=params, blocking=False)
        assert m0.wait(timeout=60)  # raises on any writer error
        assert m1.wait(timeout=60)
    finally:
        stop.set()
        t.join(10)
    assert not problems, problems[:5]
    # the retention window held: exactly the newest complete step left
    assert ckpt.latest_step(d, num_ranks=2) == 25


# ---------------------------------------------------------------------
# checkpoint integrity: manifests, digests, verified fallback, CLI
# ---------------------------------------------------------------------
def _corrupt(path, offset=40, junk=b"\xde\xad\xbe\xef"):
    with open(path, "r+b") as f:
        f.seek(offset)
        f.write(junk)


def test_manifest_written_with_digests_and_tree(tmp_path):
    d = str(tmp_path / "ck")
    params = {"w": np.arange(6, dtype="f4").reshape(2, 3)}
    ckpt.save_checkpoint(d, 2, params=params)
    man = ckpt.read_manifest(d, 2)
    assert man is not None and man["manifest_version"] >= 1
    assert man["num_ranks"] == 1 and man["step"] == 2
    sh = man["shards"]["0"]
    assert sh["path"] == "rank0.ckpt" and sh["bytes"] > 0
    assert len(sh["sha256"]) == 64
    assert man["tree"]["params"]["w"]["shape"] == [2, 3]
    assert man["tree"]["params"]["w"]["dtype"] == "float32"
    rep = ckpt.verify_step(d, 2)
    assert rep["verified"] and not rep["corrupt"]


def test_verify_cli_audits_directory(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, 2, params={"w": np.ones(32, "f4")})
    ckpt.save_checkpoint(d, 4, params={"w": np.ones(32, "f4") * 2})
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.checkpoint", "--verify", d,
         "--json"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    rep = json.loads(res.stdout.splitlines()[-1])
    assert rep["ok"] and rep["n_verified"] == 2
    # a flipped byte fails the audit NAMING the corrupt shard
    _corrupt(ckpt.shard_path(d, 4, 0))
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.checkpoint", "--verify", d,
         "--json"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT)
    assert res.returncode == 1, res.stdout + res.stderr
    rep = json.loads(res.stdout.splitlines()[-1])
    assert not rep["ok"] and rep["n_corrupt"] == 1
    bad = [s for s in rep["steps"] if s["step"] == 4][0]
    assert bad["corrupt"] == ["rank0.ckpt"], bad


def test_load_falls_back_to_newest_verified_step(tmp_path, caplog):
    """Tentpole: a corrupt newest step is named and skipped; the load
    returns the newest VERIFIED step, bit-identical to loading that
    step explicitly (the fallback substitutes nothing else)."""
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, 2, params={"w": np.arange(16, dtype="f4")})
    ckpt.save_checkpoint(d, 4, params={"w": np.arange(16, dtype="f4") * 3})
    _corrupt(ckpt.shard_path(d, 4, 0))
    import logging

    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.checkpoint"):
        payload = ckpt.load_checkpoint(d, rank=0, num_ranks=1)
    assert payload["step"] == 2
    control = ckpt.load_checkpoint(d, step=2, rank=0, num_ranks=1)
    np.testing.assert_array_equal(payload["params"]["w"],
                                  control["params"]["w"])
    text = " ".join(r.getMessage() for r in caplog.records)
    assert "rank0.ckpt" in text and "falling back" in text, text


def test_explicit_step_corrupt_fails_fast(tmp_path):
    """Satellite: an explicitly requested step (resume_from pointing at
    a step dir included) NEVER silently substitutes another one."""
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, 2, params={"w": np.ones(8, "f4")})
    ckpt.save_checkpoint(d, 4, params={"w": np.ones(8, "f4")})
    _corrupt(ckpt.shard_path(d, 4, 0))
    with pytest.raises(ckpt.CheckpointCorrupt) as ei:
        ckpt.load_checkpoint(d, step=4, rank=0, num_ranks=1)
    assert "rank0.ckpt" in str(ei.value)
    # the step-dir spelling of resume_from is the same explicit path
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.load_checkpoint(ckpt.step_dir(d, 4), rank=0, num_ranks=1)
    # and Module.fit(resume_from=<step dir>) surfaces it, not a resume
    with pytest.raises(ckpt.CheckpointCorrupt):
        _fit(resume_from=ckpt.step_dir(d, 4))
    # verify=False opts out (documented escape hatch)
    payload = ckpt.load_checkpoint(d, step=2, rank=0, num_ranks=1,
                                   verify=False)
    assert payload["step"] == 2


def test_keep1_newest_corrupt_names_shard_clearly(tmp_path):
    """Satellite edge case: MXNET_CKPT_KEEP=1 leaves ONE step; when it
    is corrupt the fallback has nothing verified — the error must name
    the corrupt shard, not claim the checkpoint is missing."""
    d = str(tmp_path / "ck")
    mgr = ckpt.CheckpointManager(d, keep=1, async_write=False, rank=0,
                                 num_ranks=1)
    mgr.save(2, params={"w": np.ones(8, "f4")})
    mgr.save(4, params={"w": np.ones(8, "f4")})
    assert ckpt.list_steps(d) == [4]  # keep=1 dropped step 2
    _corrupt(ckpt.shard_path(d, 4, 0))
    with pytest.raises(ckpt.CheckpointCorrupt) as ei:
        ckpt.load_checkpoint(d, rank=0, num_ranks=1)
    msg = str(ei.value)
    assert "rank0.ckpt" in msg and "no verified checkpoint" in msg, msg


def test_chaos_corrupt_shard_fallback_e2e(tmp_path, monkeypatch):
    """Acceptance e2e: chaos 'corrupt_shard' flips bytes in the newest
    step's landed shard during a checkpointed fit; the resume falls
    back to the previous VERIFIED step and bitwise-matches a control
    resumed from that step explicitly."""
    from mxnet_tpu import chaos

    d = str(tmp_path / "ck")
    # steps 2,4,6 land; the step-6 shard is corrupted ON DISK by chaos
    # right after its (true) digest went into the manifest
    monkeypatch.setenv("MXNET_CHAOS", "corrupt_shard:step=6,rank=0")
    chaos.reset()
    try:
        _fit(checkpoint_every_n=2, checkpoint_dir=d)
        assert chaos.injected_total("corrupt_shard") == 1, \
            "the corruption never fired"
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
    assert ckpt.list_steps(d) == [2, 4, 6]
    assert not ckpt.verify_step(d, 6)["verified"]
    assert ckpt.verify_step(d, 4)["verified"]
    # resume (newest): silently skips corrupt step 6, resumes from 4
    resumed = _fit(resume_from=d)
    # control: resume explicitly from the verified step 4
    control = _fit(resume_from=ckpt.step_dir(d, 4))
    assert sorted(resumed) == sorted(control)
    for k in control:
        np.testing.assert_array_equal(resumed[k].asnumpy(),
                                      control[k].asnumpy())


def test_janitor_never_deletes_step_being_verified(tmp_path):
    """Satellite stress: the retention janitor (keep=1) races readers
    that digest-verify every load.  The manifest/tombstone/pin barrier
    must guarantee a reader NEVER sees a half-deleted step as corrupt
    — every load either verifies clean or reports the step gone."""
    d = str(tmp_path / "race")
    m0 = ckpt.CheckpointManager(d, keep=1, async_write=False, rank=0,
                                num_ranks=1)
    params = {"w": np.arange(512, dtype="f4")}
    stop = threading.Event()
    problems = []
    n_loads = [0]

    def reader():
        while not stop.is_set():
            try:
                payload = ckpt.load_checkpoint(d, rank=0, num_ranks=1)
                n_loads[0] += 1
                if payload["params"]["w"].shape != (512,):
                    problems.append("bad payload at step %r"
                                    % payload["step"])
            except FileNotFoundError:
                pass  # GC advanced past us: legitimate
            except ckpt.CheckpointCorrupt as e:
                # the bug this test exists to catch: a half-deleted
                # step misreported as corruption
                problems.append("spurious corruption: %s" % e)
            except Exception as e:
                problems.append(repr(e))

    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for step in range(1, 40):
            m0.save(step, params=params)
    finally:
        stop.set()
        for t in threads:
            t.join(10)
    assert not problems, problems[:5]
    assert n_loads[0] > 0, "the readers never overlapped the janitor"
    assert ckpt.latest_step(d, num_ranks=1) == 39


# ---------------------------------------------------------------------
# elastic resume: W-rank checkpoints load on W'-rank fleets
# ---------------------------------------------------------------------
def test_elastic_load_reshards_deterministically(tmp_path):
    d = str(tmp_path / "ck2")
    for r in (0, 1):
        ckpt.CheckpointManager(d, rank=r, num_ranks=2,
                               async_write=False).save(
            4, params={"w": np.full(4, r, "f4")}, epoch=1, nbatch=1,
            optimizer_states=b"momenta" if r == 0 else None,
            iterator_state={"cursor": 4, "batch_size": 4})
    # W=2 -> W'=1: rank 0 reads source shard 0 (momenta included)
    p = ckpt.load_checkpoint(d, rank=0, num_ranks=1)
    el = p["elastic"]
    assert (el["from_num_ranks"], el["to_num_ranks"]) == (2, 1)
    assert el["source_rank"] == 0 and p["optimizer_states"] == b"momenta"
    # global sample position invariant: 1 batch x 4/rank x 2 ranks = 8
    # samples -> 1 global batch of 8, or 2 of 4, on the single rank
    assert ckpt.scale_resume_skip(p, 8) == 1
    assert ckpt.scale_resume_skip(p, 4) == 2
    # W=2 -> W'=3: ranks wrap deterministically (r % W)
    p2 = ckpt.load_checkpoint(d, rank=2, num_ranks=3)
    assert p2["elastic"]["source_rank"] == 0
    np.testing.assert_array_equal(p2["params"]["w"], 0)
    # W == W': no elastic marker, the bitwise contract path
    same = ckpt.load_checkpoint(d, rank=1, num_ranks=2)
    assert "elastic" not in same
    np.testing.assert_array_equal(same["params"]["w"], 1)


def _combined_iter(batch_size=8):
    """The two ft_worker ranks' per-rank streams interleaved per step:
    global batch i = rank0's batch i ++ rank1's batch i — what a
    single-rank fleet must consume to replay the SAME global batch
    sequence the 2-rank fleet trained on."""
    streams = []
    for rank in (0, 1):
        rng = np.random.RandomState(100 + rank)
        x = rng.randn(12, 6).astype(np.float32)
        y = rng.randint(0, 4, (12,)).astype(np.float32)
        streams.append((x, y))
    xs, ys = [], []
    for i in range(3):
        for rank in (0, 1):
            xs.append(streams[rank][0][i * 4:(i + 1) * 4])
            ys.append(streams[rank][1][i * 4:(i + 1) * 4])
    return mx.io.NDArrayIter(np.concatenate(xs), np.concatenate(ys),
                             batch_size=batch_size, shuffle=False)


def _ft_mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=8)
    net = sym.Activation(data=net, act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=4)
    return sym.SoftmaxOutput(data=net, name="softmax")


def test_elastic_resume_across_world_sizes_e2e(tmp_path):
    """Acceptance: a 2-rank checkpoint resumes on 1 rank (and a 1-rank
    checkpoint resumes on 2 ranks) with final params matching the
    2-rank control at ~1e-7 on the CPU mesh — the global batch
    sequence is preserved (per-rank batch x world size invariant), so
    only summation order differs."""
    import launch as _launch

    base_env = {"MXNET_CKPT_ASYNC": "0", "MXNET_CKPT_KEEP": "0",
                "MXNET_DUMP_DIR": str(tmp_path / "dumps")}
    ck2 = str(tmp_path / "ck2rank")

    # 2-rank control: uninterrupted, checkpoints every 2 steps (kept)
    codes = _launch.launch_local(
        2, 1, [sys.executable, _FT_WORKER, "control", ck2,
               str(tmp_path / "control")],
        env=_child_env(base_env))
    assert codes == [0, 0], codes
    control = {r: np.load(str(tmp_path / ("control_rank%d.npz" % r)))
               for r in (0, 1)}
    assert ckpt.read_manifest(ck2, 4) is not None \
        and ckpt.read_manifest(ck2, 4)["num_ranks"] == 2

    def _fit_combined(**kw):
        np.random.seed(0)
        mx.random.seed(0)
        mod = mx.mod.Module(symbol=_ft_mlp(), context=mx.cpu())
        mod.fit(_combined_iter(), optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "rescale_grad": 1.0, "wd": 0.0},
                num_epoch=2, **kw)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    # (a) 2 -> 1: resume in-process from the 2-rank step-4 shard with
    # the combined global-batch stream; finals match the 2-rank control
    resumed = _fit_combined(resume_from=ckpt.step_dir(ck2, 4))
    for k in control[0].files:
        np.testing.assert_allclose(
            resumed[k], control[0][k], rtol=2e-6, atol=1e-7,
            err_msg="2->1 elastic resume diverged on %s" % k)

    # (b) 1 -> 2: a 1-rank run checkpoints the same global stream;
    # a 2-worker fleet elastically resumes its step-4 and must also
    # match the 2-rank control
    ck1 = str(tmp_path / "ck1rank")
    _fit_combined(checkpoint_every_n=2, checkpoint_dir=ck1)
    import shutil

    shutil.rmtree(ckpt.step_dir(ck1, 6))  # pretend it died after step 4
    codes = _launch.launch_local(
        2, 1, [sys.executable, _FT_WORKER, "resume", ck1,
               str(tmp_path / "elastic2")],
        env=_child_env(base_env))
    assert codes == [0, 0], codes
    for r in (0, 1):
        resumed2 = np.load(str(tmp_path / ("elastic2_rank%d.npz" % r)))
        for k in control[r].files:
            np.testing.assert_allclose(
                resumed2[k], control[r][k], rtol=2e-6, atol=1e-7,
                err_msg="1->2 elastic resume diverged on rank %d %s"
                        % (r, k))


# ---------------------------------------------------------------------
# exact resume (single process; the dist version is the e2e below)
# ---------------------------------------------------------------------
def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=8)
    net = sym.Activation(data=net, act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=4)
    return sym.SoftmaxOutput(data=net, name="softmax")


def _iter():
    rng = np.random.RandomState(7)
    x = rng.randn(24, 6).astype(np.float32)
    y = rng.randint(0, 4, (24,)).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=8, shuffle=False)


def _fit(**kw):
    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(symbol=_mlp(), context=mx.cpu())
    mod.fit(_iter(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            num_epoch=2, **kw)
    return mod.get_params()[0]


def test_fit_resume_bitwise(tmp_path):
    """The exact-resume guarantee: interrupt at a checkpoint boundary,
    resume in a FRESH module, and the final params bitwise-match the
    uninterrupted control (params + momenta + iterator position all
    round-tripped)."""
    d = str(tmp_path)
    control = _fit()
    with_ckpt = _fit(checkpoint_every_n=2, checkpoint_dir=d)
    for k in control:  # checkpointing must not perturb training
        np.testing.assert_array_equal(control[k].asnumpy(),
                                      with_ckpt[k].asnumpy())
    assert ckpt.list_steps(d) == [2, 4, 6]
    # pretend the run died after step 4: drop the final checkpoint and
    # resume — 2 steps replay across the epoch boundary
    import shutil

    shutil.rmtree(ckpt.step_dir(d, 6))
    resumed = _fit(resume_from=d)
    assert sorted(control) == sorted(resumed)
    for k in control:
        np.testing.assert_array_equal(control[k].asnumpy(),
                                      resumed[k].asnumpy())


def _fit_pipe(bulk=0, **kw):
    """Module.fit driven by the sharded decode pool + async device
    prefetch (io_pipeline.InputPipeline) instead of a plain iterator."""
    from mxnet_tpu import engine
    from mxnet_tpu import io_pipeline as iop

    rng = np.random.RandomState(7)
    x = rng.randn(24, 6).astype(np.float32)
    y = rng.randint(0, 4, (24,)).astype(np.float32)
    np.random.seed(0)
    mx.random.seed(0)
    pipe = iop.InputPipeline(
        iop.make_ndarray_iter_fn(x, y, batch_size=8), num_workers=2,
        device=True)
    try:
        with engine.bulk(bulk) if bulk else contextlib.nullcontext():
            mod = mx.mod.Module(symbol=_mlp(), context=mx.cpu())
            mod.fit(pipe, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9},
                    num_epoch=2, **kw)
    finally:
        pipe.close()
    return mod.get_params()[0]


@pytest.mark.parametrize("bulk", [0, 2], ids=["per_batch", "bulk"])
def test_fit_resume_bitwise_with_io_pipeline(tmp_path, bulk):
    """The exact-resume contract THROUGH the new input pipeline: the
    decode pool + async device prefetch active on both the per-batch
    and bulk-scan fit paths, checkpoint mid-epoch, resume in a fresh
    module over a fresh pool — bitwise parity with the uninterrupted
    control (the pool's round-robin stream is deterministic, and
    skip_batches fast-forwards to the exact position)."""
    d = str(tmp_path)
    control = _fit_pipe(bulk=bulk)
    with_ckpt = _fit_pipe(bulk=bulk, checkpoint_every_n=2,
                          checkpoint_dir=d)
    for k in control:  # checkpointing through the pool is invisible
        np.testing.assert_array_equal(control[k].asnumpy(),
                                      with_ckpt[k].asnumpy())
    steps = ckpt.list_steps(d)
    assert steps, "no checkpoints landed"
    # pretend the run died: drop the newest step and resume mid-epoch
    import shutil

    shutil.rmtree(ckpt.step_dir(d, steps[-1]))
    assert ckpt.list_steps(d), "need a mid-run step to resume from"
    resumed = _fit_pipe(bulk=bulk, resume_from=d)
    assert sorted(control) == sorted(resumed)
    for k in control:
        np.testing.assert_array_equal(control[k].asnumpy(),
                                      resumed[k].asnumpy())


def test_fit_nan_guard_skips_step(monkeypatch):
    """chaos nan_grad at step 3 + MXNET_SKIP_NONFINITE_GRADS: the step
    is skipped/neutralized (no NaN reaches the params), the skip
    counter increments, and training continues to finite params."""
    from mxnet_tpu import chaos, diagnostics

    monkeypatch.setenv("MXNET_SKIP_NONFINITE_GRADS", "1")
    monkeypatch.setenv("MXNET_CHAOS", "nan_grad:step=3")
    chaos.reset()
    skip = diagnostics.metrics.counter(
        "mxnet_training_skipped_steps_total")
    before = skip.value
    try:
        params = _fit()
        injected = chaos.injected_total("nan_grad")
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
    assert injected == 1, "the NaN fault never fired"
    assert skip.value == before + 1
    for k, v in params.items():
        assert np.isfinite(v.asnumpy()).all(), k


# ---------------------------------------------------------------------
# kvstore retry/backoff (unit, injected transport failures)
# ---------------------------------------------------------------------
class _FlakyServer(threading.Thread):
    """Accepts connections; drops the first N exchanges (reads the
    request then closes — the 'response lost' case), then serves
    {"ok": True, "echo": op} forever."""

    def __init__(self, drop_first=1):
        super().__init__(daemon=True)
        from mxnet_tpu import _ps

        self._ps = _ps
        self.drop_left = drop_first
        self.served = 0
        self.lst = socket.socket()
        self.lst.bind(("127.0.0.1", 0))
        self.lst.listen(8)
        self.addr = self.lst.getsockname()
        self.start()

    def run(self):
        while True:
            try:
                conn, _ = self.lst.accept()
            except OSError:
                return
            try:
                while True:
                    msg = self._ps.recv_msg(conn)
                    if msg is None:
                        break
                    if self.drop_left > 0:
                        self.drop_left -= 1
                        break  # close without replying: response lost
                    self.served += 1
                    self._ps.send_msg(conn, {"ok": True,
                                             "echo": msg.get("op")})
            finally:
                conn.close()

    def close(self):
        self.lst.close()


def _bare_dist(addr):
    """A KVStoreDist shell wired to one server address — enough for the
    transport layer, no scheduler/cluster needed."""
    from mxnet_tpu import _ps
    from mxnet_tpu.kvstore import KVStoreDist

    kvd = KVStoreDist.__new__(KVStoreDist)
    kvd._ps = _ps
    kvd._server_addrs = [tuple(addr)]
    kvd._server_clients = [_ps.Client(addr)]
    kvd._reconnect_lock = threading.Lock()
    kvd._pseq = {}
    kvd._pseq_lock = threading.Lock()
    return kvd


def test_retry_absorbs_dropped_response(monkeypatch):
    monkeypatch.setenv("MXNET_PS_RETRY_MAX", "3")
    monkeypatch.setenv("MXNET_PS_RETRY_BACKOFF_S", "0.01")
    srv = _FlakyServer(drop_first=1)
    try:
        kvd = _bare_dist(srv.addr)
        t0 = time.time()
        resp = kvd._req_server(0, {"op": "pull", "key": "k", "worker": 0})
        assert resp["echo"] == "pull"
        assert srv.served == 1
        assert time.time() - t0 < 10
    finally:
        srv.close()


def test_retry_gives_up_after_max(monkeypatch):
    from mxnet_tpu.base import MXNetError

    monkeypatch.setenv("MXNET_PS_RETRY_MAX", "2")
    monkeypatch.setenv("MXNET_PS_RETRY_BACKOFF_S", "0.01")
    srv = _FlakyServer(drop_first=100)  # never recovers
    try:
        kvd = _bare_dist(srv.addr)
        with pytest.raises(MXNetError, match="after 3 attempt"):
            kvd._req_server(0, {"op": "init", "key": "k", "data": 1})
    finally:
        srv.close()


def test_control_ops_fail_fast(monkeypatch):
    """A lost 'stop' ack must NOT be resent (double-counted shutdown
    would end the server under its peers)."""
    from mxnet_tpu.base import MXNetError

    monkeypatch.setenv("MXNET_PS_RETRY_MAX", "5")
    monkeypatch.setenv("MXNET_PS_RETRY_BACKOFF_S", "0.01")
    srv = _FlakyServer(drop_first=1)
    try:
        kvd = _bare_dist(srv.addr)
        with pytest.raises(MXNetError):
            kvd._req_server(0, {"op": "stop"})
        assert srv.served == 0
    finally:
        srv.close()


def test_server_dedupes_resent_pseq():
    """The server half of exactly-once: a push resent with the same
    pseq is acked but not re-applied."""
    from mxnet_tpu.kvstore_server import KVStoreServer, _KeyState

    srv = KVStoreServer.__new__(KVStoreServer)
    srv.sync_mode = True
    srv.num_workers = 1
    srv.store, srv.state = {}, {}
    srv.updater = None
    srv.gc = None
    srv.lock = threading.Condition()
    msg = {"op": "push", "key": "k", "worker": 0, "pseq": 1,
           "data": np.ones((2,), np.float32)}
    assert srv._handle_push(dict(msg)) is True
    assert srv._handle_push(dict(msg)) is False  # dup: ack, no apply
    st = srv.state["k"]
    assert st.pushed_by[0] == 1 and st.applied == 1
    np.testing.assert_allclose(srv.store["k"], 1.0)
    assert srv._handle_push(dict(msg, pseq=2)) is True  # next round
    assert st.pushed_by[0] == 2

    # recovery rejoin: worker_hello hands back the pushed_by high water
    # so a restarted worker (fresh pseq counters) is NOT dedupe-starved
    import socket as _socket

    from mxnet_tpu import _ps

    a, b = _socket.socketpair()
    try:
        _ps.send_msg(a, {"op": "worker_hello", "worker": 0,
                         "recovery": True})
        assert srv._dispatch(b, _ps.recv_msg(b)) in (None, False)
        reply = _ps.recv_msg(a)
        assert reply["pseq"] == {"k": 2}, reply
    finally:
        a.close()
        b.close()
    # a rejoined worker continuing from the high water applies normally
    assert srv._handle_push(dict(msg, pseq=3)) is True
    assert st.pushed_by[0] == 3


def test_resume_on_epoch_boundary_no_duplicate_tail(tmp_path):
    """A checkpoint taken on an epoch's LAST batch resumes into the
    NEXT epoch: the already-finished epoch must not re-fire its
    epoch-end callbacks or score an empty metric."""
    d = str(tmp_path)
    control = _fit()
    # 3 steps/epoch, every_n=3 -> shards at exact epoch boundaries
    _fit(checkpoint_every_n=3, checkpoint_dir=d)
    assert ckpt.list_steps(d) == [3, 6]
    import shutil

    shutil.rmtree(ckpt.step_dir(d, 6))  # died right after epoch 0
    epochs_ended = []
    resumed = _fit(resume_from=d,
                   epoch_end_callback=lambda e, *a: epochs_ended.append(e))
    # only epoch 1 runs (and ends) in the resumed process
    assert epochs_ended == [1], epochs_ended
    for k in control:
        np.testing.assert_array_equal(control[k].asnumpy(),
                                      resumed[k].asnumpy())


# ---------------------------------------------------------------------
# preemption: SIGTERM ordering + exit code (subprocess)
# ---------------------------------------------------------------------
_SIGTERM_SCRIPT = r"""
import os, signal, sys, time
import mxnet_tpu  # noqa
from mxnet_tpu import diagnostics as diag

marker = sys.argv[1]
seq = diag.record_start("push", keys=["k"], nbytes=4)  # arms handlers
diag.record_complete(seq)

def hook():
    # ordering proof: when the checkpoint hook runs, the flight dump
    # (step 1) must already be on disk
    with open(marker, "w") as f:
        f.write("dump_exists=%s" % os.path.exists(diag.recorder.dump_path()))

diag.register_preemption_hook(hook)
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(30)
sys.exit(7)  # must not be reached
"""


def test_sigterm_dump_checkpoint_exit_ordering(tmp_path):
    script = tmp_path / "sigterm.py"
    script.write_text(_SIGTERM_SCRIPT)
    marker = tmp_path / "hook_ran"
    res = subprocess.run(
        [sys.executable, str(script), str(marker)],
        capture_output=True, text=True, timeout=120,
        env=_child_env({
            "MXNET_FLIGHT_RECORDER_DUMP": "1",
            "MXNET_FLIGHT_RECORDER_FILE":
                str(tmp_path / "flightrecorder.json"),
            "MXNET_CKPT_DRAIN_S": "0.5",
        }), cwd=ROOT)
    from mxnet_tpu.diagnostics import EXIT_PREEMPTED

    assert res.returncode == EXIT_PREEMPTED, (res.returncode, res.stderr)
    assert marker.read_text() == "dump_exists=True"
    dump = tmp_path / "flightrecorder_rank0.json"
    assert dump.exists()
    with open(dump) as f:
        assert json.load(f)["header"]["reason"] == "SIGTERM"


def test_sigterm_without_hooks_still_chains(tmp_path):
    """No preemption hook registered -> the pre-existing contract:
    dump, then chain to the default action (die by SIGTERM)."""
    script = tmp_path / "chain.py"
    script.write_text(
        "import os, signal, time, mxnet_tpu\n"
        "from mxnet_tpu import diagnostics as diag\n"
        "s = diag.record_start('push', keys=['k'], nbytes=4)\n"
        "diag.record_complete(s)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(30)\n")
    res = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=120, env=_child_env({
            "MXNET_FLIGHT_RECORDER_DUMP": "1",
            "MXNET_FLIGHT_RECORDER_FILE":
                str(tmp_path / "flightrecorder.json"),
            "MXNET_CKPT_DRAIN_S": "0.2",
        }), cwd=ROOT)
    assert res.returncode == -signal.SIGTERM, res.returncode
    assert (tmp_path / "flightrecorder_rank0.json").exists()


# ---------------------------------------------------------------------
# watchdog escalation: permanent desync -> checkpointed abort (code 85)
# ---------------------------------------------------------------------
_WATCHDOG_SCRIPT = r"""
import sys, time
import mxnet_tpu  # noqa
from mxnet_tpu import diagnostics as diag

diag.register_preemption_hook(
    lambda: open(sys.argv[1], "w").write("checkpointed"))
# a collective that never completes: the permanent-desync shape the
# watchdog must convert from an infinite hang into a restartable abort
diag.record_start("allreduce", keys=["w3"], bucket=7, nbytes=1 << 20)
time.sleep(60)
sys.exit(7)  # must not be reached
"""


def test_watchdog_escalation_aborts_with_code(tmp_path):
    script = tmp_path / "wd.py"
    script.write_text(_WATCHDOG_SCRIPT)
    marker = tmp_path / "ckpt_marker"
    t0 = time.time()
    res = subprocess.run(
        [sys.executable, str(script), str(marker)],
        capture_output=True, text=True, timeout=120,
        env=_child_env({
            "MXNET_COLLECTIVE_TIMEOUT_S": "0.3",
            "MXNET_COLLECTIVE_ABORT_S": "1.0",
            "MXNET_FLIGHT_RECORDER_FILE":
                str(tmp_path / "flightrecorder.json"),
        }), cwd=ROOT)
    from mxnet_tpu.diagnostics import EXIT_WATCHDOG_ABORT

    assert res.returncode == EXIT_WATCHDOG_ABORT, \
        (res.returncode, res.stderr)
    assert time.time() - t0 < 60, "abort threshold did not fire promptly"
    assert marker.read_text() == "checkpointed"
    dump = tmp_path / "flightrecorder_rank0.json"
    assert dump.exists()
    with open(dump) as f:
        payload = json.load(f)
    assert payload["header"]["reason"] == "watchdog_abort"
    assert payload["entries"][0]["state"] in ("in_flight", "suspect")


# ---------------------------------------------------------------------
# MXNET_DUMP_DIR: artifacts out of the CWD (the repo-littering fix)
# ---------------------------------------------------------------------
def test_dump_dir_redirects_relative_artifacts(tmp_path, monkeypatch):
    from mxnet_tpu.diagnostics import FlightRecorder

    monkeypatch.setenv("MXNET_DUMP_DIR", str(tmp_path / "artifacts"))
    fr = FlightRecorder(capacity=4)
    s = fr.start("push", keys=["k"], nbytes=8)
    fr.complete(s)
    path = fr.dump()
    assert path is not None and path.startswith(str(tmp_path))
    assert os.path.exists(path)
    # absolute paths always win
    explicit = str(tmp_path / "explicit.json")
    assert fr.dump(path=explicit) == explicit


# ---------------------------------------------------------------------
# e2e: chaos drop absorbed by retry in a real cluster
# ---------------------------------------------------------------------
def _run_cluster(kind, num_workers, num_servers, extra_env=None):
    codes = launch.launch_local(
        num_workers, num_servers,
        [sys.executable, _DIST_WORKER, kind],
        env=dict(_child_env(extra_env)))
    assert codes == [0] * num_workers, "worker failures: %s" % codes


def test_chaos_dropped_push_absorbed_e2e():
    """Acceptance: an injected dropped push (response lost AFTER server
    apply — the hard case) is absorbed by retry/backoff + pseq dedupe
    with exact sync arithmetic and no operator intervention."""
    _run_cluster("chaos_drop", 2, 1, extra_env={
        "MXNET_CHAOS": "drop_push:rank=1,nth=2",
        "MXNET_PS_RETRY_MAX": "3",
        "MXNET_PS_RETRY_BACKOFF_S": "0.05",
    })


# ---------------------------------------------------------------------
# e2e: kill rank 1 mid-step, restart, resume == control (bitwise)
# ---------------------------------------------------------------------
def test_kill_and_resume_matches_control(tmp_path):
    """The tentpole acceptance test: a 2-worker dist_sync fit is killed
    on rank 1 mid-step by chaos injection; the surviving rank's flight
    dump names the dead peer; a fresh cluster resumes from the newest
    complete checkpoint and the final params bitwise-match an
    uninterrupted control run."""
    ckpt_dir = str(tmp_path / "ckpt")
    base_env = {
        "MXNET_CKPT_ASYNC": "0",  # deterministic shard set at the kill
        "MXNET_PS_HEARTBEAT_INTERVAL": "0.2",
        "MXNET_KVSTORE_SYNC_TIMEOUT": "8",
        "MXNET_DUMP_DIR": str(tmp_path / "dumps"),
    }

    # control: uninterrupted
    codes = launch.launch_local(
        2, 1, [sys.executable, _FT_WORKER, "control", ckpt_dir + "_c",
               str(tmp_path / "control")],
        env=_child_env(base_env))
    assert codes == [0, 0], codes

    # victim: rank 1 is killed mid-step 5 (after backward, before
    # update); rank 0's sync pull times out and the fleet dies
    codes = launch.launch_local(
        2, 1, [sys.executable, _FT_WORKER, "victim", ckpt_dir,
               str(tmp_path / "victim")],
        env=_child_env(dict(base_env, **{
            "MXNET_CHAOS": "kill:rank=1,step=5",
            "MXNET_FLIGHT_RECORDER_DUMP": "1",
            "MXNET_FLIGHT_RECORDER_FILE":
                str(tmp_path / "flightrecorder.json"),
        })))
    from mxnet_tpu.chaos import KILL_EXIT_CODE

    assert KILL_EXIT_CODE in codes, codes
    assert codes != [0, 0], "the kill never fired: %s" % codes
    assert ckpt.latest_step(ckpt_dir, num_ranks=2) == 4

    # the surviving rank's dump names the dead peer; --health reports it
    dump0 = tmp_path / "flightrecorder_rank0.json"
    assert dump0.exists(), "rank 0 left no flight dump"
    with open(dump0) as f:
        header = json.load(f)["header"]
    assert "worker:1" in header.get("dead_peers", []), header
    tool = os.path.join(ROOT, "tools", "merge_traces.py")
    res = subprocess.run(
        [sys.executable, tool, "--health", str(dump0)],
        capture_output=True, text=True)
    assert res.returncode == 2, (res.returncode, res.stdout)
    assert "DEAD PEER (heartbeat): worker:1" in res.stdout, res.stdout

    # resume: fresh cluster picks up from step 4 and finishes
    codes = launch.launch_local(
        2, 1, [sys.executable, _FT_WORKER, "resume", ckpt_dir,
               str(tmp_path / "resumed")],
        env=_child_env(base_env))
    assert codes == [0, 0], codes

    for rank in range(2):
        control = np.load(str(tmp_path / ("control_rank%d.npz" % rank)))
        resumed = np.load(str(tmp_path / ("resumed_rank%d.npz" % rank)))
        assert sorted(control.files) == sorted(resumed.files)
        for k in control.files:
            np.testing.assert_array_equal(
                control[k], resumed[k],
                err_msg="rank %d param %s diverged after resume" % (rank, k))
