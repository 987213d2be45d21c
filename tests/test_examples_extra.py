"""Round-3 example families (VERDICT r2 item 10): sparse linear
classification, mini Faster-RCNN (Proposal+ROIPooling jointly), neural
style (autograd on inputs), FGSM adversary.  Each runs CI-size as a
subprocess — the scripts' own PASS assertions are the contract."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(script, args=(), timeout=900):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script)]
        + list(args), env=env, capture_output=True, text=True,
        timeout=timeout)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "PASS" in proc.stdout, proc.stdout[-2000:]
    return proc.stdout


@pytest.mark.slow
def test_sparse_linear_classification():
    _run("sparse/linear_classification.py")


@pytest.mark.slow
def test_adversary_fgsm():
    _run("adversary/fgsm.py")


@pytest.mark.slow
def test_neural_style():
    _run("neural_style/nstyle.py")


@pytest.mark.slow
def test_mini_rcnn():
    _run("rcnn/mini_rcnn.py")
