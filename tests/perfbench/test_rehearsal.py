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
# what each toy cell reports is what the committed manifest says of the
# cell it stands for
END_TO_END = {
    cell: {m["name"] for m in toy_manifest.build()["end_to_end"]
           if cell in m.get("workloads", [cell])} for cell in CELLS}
# the per-layer metrics that a run without a chip reads: the host's
# clock, and what the program counts
HOST_METRICS = {
    "toy_image": {"dispatch_ms.train"}, "toy_lm": {"dispatch_ms.train"},
    "toy_serve": {"queue_ms_p90", "loadgen_late_p90_ms", "ttft_p90_ms",
                  "ttft_p50_ms", "batch_occupancy_pct",
                  "kv_live_bytes_p50"},
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


def test_end_to_end_metrics_are_the_manifests():
    assert {"train_step_ms", "setup_s"} == END_TO_END["toy_lm"] \
        == END_TO_END["toy_image"]
    assert {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"} \
        == END_TO_END["toy_serve"]


def test_the_serving_window_opens_on_a_warmed_server(_run):
    """The toy cell's 0.3 s of warm-up: its requests are sent, are no
    part of ``attempted``, and the window's first fifth finds slots
    occupied; the cache's live bytes are whole blocks."""
    rc, out, _ = _run("toy_serve", 1, seed=23)
    assert rc == 0
    line = json.loads(out[-1])
    info = next(ln for ln in out if "of warm-up" in ln)
    # 40 a second: 20 due in the window's 0.5 s, 12 in the warm-up
    assert line["attempted"] in range(18, 23)
    assert "more in 0.3 s of warm-up" in info
    assert int(info.split("(")[1].split()[0]) in range(10, 15)
    first_fifth = float(info.split("slots occupied ")[1].split()[0])
    assert first_fifth > 0.0
    assert 0.0 < line["metrics"]["batch_occupancy_pct"]["value"] <= 100.0
    with open(os.path.join(TOY, "workloads", "toy_serve.json")) as f:
        cell = json.load(f)
    with open(os.path.join(TOY, "configs", "dense_toy_serve.json")) as f:
        cfg = json.load(f)
    block = cell["block_tokens"] * 2 * cfg["num_hidden_layers"] \
        * cfg["hidden_size"] * 4                   # float32 cache
    held = line["metrics"]["kv_live_bytes_p50"]["value"]
    # whole blocks; 0 where the median send found the toy server idle
    assert held >= 0 and held % block == 0


def test_a_plan_cell_compiled_under_traffic_fails_the_run(_run,
                                                          monkeypatch):
    from perfbench.drivers import serve_lm

    counts = iter([{"gen_decode:x": 1}, {"gen_decode:x": 2}])
    monkeypatch.setattr(serve_lm.Driver, "_compiles",
                        staticmethod(lambda: next(counts)))
    with pytest.raises(RuntimeError, match="compiled under traffic"):
        _run("toy_serve", 0, seed=29)


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


SERVE_FAULTS = ["decode_position_before", "decode_first_block_stale",
                "decode_tables_rolled"]


@pytest.fixture(scope="module")
def _planted(tmp_path_factory):
    """``perfbench.calibrate --faults 1`` on the toy serving cell, once:
    its lines by what they read."""
    import contextlib
    import io

    from perfbench import calibrate

    path = toy_manifest.write(tmp_path_factory.mktemp("planted"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = calibrate.main(["--workload", "toy_serve", "--seeds", "6",
                             "--faults", "1"],
                            manifest_path=path, data_root=TOY)
    assert rc == 0
    return {ln["what"]: ln for ln in map(json.loads, (
        ln for ln in out.getvalue().splitlines() if ln.startswith("{")))}


@pytest.mark.parametrize("fault", SERVE_FAULTS)
def test_a_planted_cache_fault_fails_the_toy_cells_number(_planted, fault):
    """What the engine hands a compiled decode step is altered on its
    way in (a position, a block, a table row), under the same server,
    after the program's own window, which stays within the limit."""
    with open(os.path.join(TOY, "workloads", "toy_serve.json")) as f:
        cell = json.load(f)
    assert fault in cell["faults"]
    limit = cell["limits"]["token_gap"]
    assert _planted["program"]["token_gap"] <= limit
    assert _planted[fault]["token_gap"] > limit

