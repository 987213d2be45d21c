"""chip_smoke.py's contract, as far as a CPU box can check it.

The chip run itself happens through the chip tool; here:

* the default invocation on a backend without a TPU exits non-zero,
  names the platform it found and prints no result;
* ``--rehearsal`` drives every leg's control flow at toy sizes on two
  virtual CPU devices under jax's default x32 (the chip's setting — the
  suite's own x64 is stripped), Pallas in interpret mode, and labels
  itself as not a chip result.  The legs are spread over three
  processes that run side by side, to keep tier-1's wall clock down;
* a leg that cannot run is an exception out of ``main`` — a non-zero
  exit — not a swallowed error.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

_RUNS = {
    "default": [],
    "resnet": ["--rehearsal", "--legs", "resnet"],
    "lm": ["--rehearsal", "--legs", "transformer,multichip"],
    "serve": ["--rehearsal", "--legs", "serving,pallas"],
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every invocation at once: {name: (returncode, stdout, stderr,
    out_dir)}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("JAX_ENABLE_X64", None)
    base = tmp_path_factory.mktemp("chip_smoke")
    procs = {}
    for name, args in _RUNS.items():
        out = base / name
        procs[name] = (subprocess.Popen(
            [sys.executable, SCRIPT, "--out", str(out)] + args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(base)), out)
    done = {}
    for name, (proc, out) in procs.items():
        stdout, stderr = proc.communicate(timeout=600)
        done[name] = (proc.returncode, stdout, stderr, out)
    return done


def test_default_invocation_refuses_a_backend_without_tpu(runs):
    rc, stdout, stderr, out = runs["default"]
    assert rc not in (0, None), stdout + stderr
    assert "found platform 'cpu'" in stderr, stderr
    assert '"ok"' not in stdout, stdout
    assert not (out / "chip_smoke.json").exists()


def test_rehearsal_covers_every_leg(runs):
    legs = {}
    for name in ("resnet", "lm", "serve"):
        rc, stdout, stderr, out = runs[name]
        assert rc == 0, stdout[-3000:] + stderr[-3000:]
        assert "REHEARSAL — NOT A CHIP RESULT" in stdout
        assert "x64=False" in stdout
        last = json.loads(stdout.strip().splitlines()[-1])
        assert "ok" not in last
        assert last["rehearsal"] == "not a chip result"
        assert last["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 2}
        with open(out / "chip_smoke.json") as f:
            legs.update(json.load(f)["legs"])
    assert sorted(legs) == ["multichip", "pallas", "resnet", "serving",
                            "transformer"]
    assert all("wall_s" in v and "compile" in v for v in legs.values())
    assert legs["pallas"]["lowering"] == "interpret"
    assert legs["resnet"]["input_pipeline"]["batches"] >= 2
    for model in ("resnet", "transformer"):
        one, both = (legs["multichip"][model][k]
                     for k in ("loss_one_chip", "loss_all_chips"))
        assert abs(one - both) <= 2e-2 * max(1.0, abs(one)), model


def test_a_leg_that_cannot_run_fails_the_script(tmp_path, monkeypatch):
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    # a sequence the kernel's blocks do not divide
    monkeypatch.setitem(chip_smoke.REHEARSAL, "attn", (1, 24, 2, 8))
    with pytest.raises(ValueError, match="do not divide"):
        chip_smoke.main(["--rehearsal", "--legs", "pallas",
                         "--out", str(tmp_path)])
    assert not (tmp_path / "chip_smoke.json").exists()
