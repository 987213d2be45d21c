"""Each driver end to end on the CPU at a toy configuration, through
``perfbench.run.main`` with only the look for a chip lifted; and the
same with the timed path broken underneath, where ``correct`` has to
come out false."""
import json
import os

import pytest

from perfbench import run

import toy_manifest

TOY = toy_manifest.TOY
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    return toy_manifest.write(tmp_path_factory.mktemp("toy"))


@pytest.fixture
def _run(capsys, manifest_path):
    def call(cell, trace, seed=3000000019, require_chip=False):
        return _call(capsys, manifest_path, cell, trace, seed,
                     require_chip)
    return call


def _call(capsys, manifest_path, cell, trace, seed, require_chip):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)],
                  manifest_path=manifest_path,
                  data_root=TOY, require_chip=require_chip)
    captured = capsys.readouterr()
    return rc, captured.out.strip().splitlines(), captured.err


TRAIN_CELLS = ["toy_image", "toy_lm"]
CELLS = TRAIN_CELLS + ["toy_serve"]
END_TO_END = {
    "toy_image": {"train_step_ms", "setup_s"},
    "toy_lm": {"train_step_ms", "setup_s"},
    "toy_serve": {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"},
}
HOST_METRICS = {
    "toy_image": {"dispatch_ms.train"}, "toy_lm": {"dispatch_ms.train"},
    "toy_serve": {"queue_ms_p90", "loadgen_late_p90_ms", "ttft_p90_ms"},
}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_prints_the_end_to_end_metrics(_run, cell):
    rc, out, err = _run(cell, 0)
    assert rc == 0
    line = json.loads(out[-1])
    assert KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == END_TO_END[cell]
    assert all(0 < m["value"] < float("inf")
               for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    for name, c in line["compared"].items():
        assert "compared %s" % name in err


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_writes_no_cpu_number_under_a_device_name(_run, cell):
    rc, out, _ = _run(cell, 1, seed=11)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == HOST_METRICS[cell]
    assert "busy_s" not in line["device"]
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_without_a_chip_there_is_no_result(_run):
    rc, out, err = _run("toy_image", 0, require_chip=True)
    assert rc == 2 and out == [] and "no CPU fallback" in err


def _state_unchanged(monkeypatch):
    """The optimizer hands back the parameters and momenta it got."""
    from mxnet_tpu import optimizer

    monkeypatch.setattr(
        optimizer, "fused_sgd_mom_grouped",
        lambda keys, p, g, m, *a, **k: ({i: p[i] for i in keys},
                                        {i: m[i] for i in keys}))


def _half_batch(monkeypatch):
    """Both train steps see only the first half of every batch, and
    take their mean over it."""
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.transformer import TransformerTrainStep

    def halved(fn):
        def call(self, data, label):
            half = data.shape[0] // 2
            return fn(self, NDArray(getattr(data, "_data", data)[:half]),
                      NDArray(getattr(label, "_data", label)[:half]))
        return call

    monkeypatch.setattr(FusedTrainStep, "__call__",
                        halved(FusedTrainStep.__call__))
    monkeypatch.setattr(TransformerTrainStep, "step",
                        halved(TransformerTrainStep.step))


def _token_altered(monkeypatch):
    """Every fifth token is altered where the engine hands it to the
    stream."""
    from mxnet_tpu.serving.generate import GenRequest

    emit = GenRequest._emit

    def altered(self, tok):
        emit(self, tok + 1 if len(self.tokens) % 5 == 4 else tok)

    monkeypatch.setattr(GenRequest, "_emit", altered)


@pytest.mark.parametrize("cell, fault", [
    (c, f) for c in TRAIN_CELLS for f in (_state_unchanged, _half_batch)
] + [("toy_serve", _token_altered)])
def test_a_broken_timed_path_is_not_correct(_run, monkeypatch, cell,
                                            fault):
    fault(monkeypatch)
    rc, out, err = _run(cell, 0, seed=5)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is False
    assert "FAILS" in err


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(capsys, manifest_path, cell):
    """The cell's low-precision control, at toy size, through
    ``perfbench.calibrate``: the program's readings lie within the
    cell's limits and the control's pass at least one of them."""
    from perfbench import calibrate

    rc = calibrate.main(["--workload", cell, "--seeds", "4", "--control",
                         "1"],
                        manifest_path=manifest_path, data_root=TOY)
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    with open(os.path.join(TOY, "workloads", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    by_what = {ln["what"]: ln for ln in lines}
    assert all(by_what["program"][k] <= v for k, v in limits.items())
    assert any(by_what["control"][k] > v for k, v in limits.items())
