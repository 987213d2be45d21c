"""``drivers/train_moe_lm.py`` end to end on the CPU at a toy
configuration with every mechanism of the real one (latent attention
with unequal head widths and YaRN, a dense and two expert layers, four
residual streams, an untied head, one multi-token module), through
``perfbench.run.main`` with only the look for a chip lifted; with the
timed path broken underneath, where ``correct`` has to come out false;
and the cell's control and planted faults through
``perfbench.calibrate``."""
import json
import os

import pytest

from perfbench import run, validate

import toy_moe_manifest

TOY = toy_moe_manifest.TOY
CELL = "toy_moe_lm"


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    return toy_moe_manifest.write(tmp_path_factory.mktemp("toy_moe"))


def _run(capsys, manifest_path, trace, seed):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)],
                  manifest_path=manifest_path, data_root=TOY,
                  require_chip=False)
    captured = capsys.readouterr()
    return rc, captured.out.strip().splitlines(), captured.err


def test_toy_manifest_is_sound():
    assert validate.check(toy_moe_manifest.build(), toy_moe_manifest.ROOT,
                          TOY) == []


def test_untraced_run_prints_the_end_to_end_metrics(capsys, manifest_path):
    rc, out, err = _run(capsys, manifest_path, 0, 3000000019)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "change_gap",
                                     "route_disagree_pct"}
    assert line["compared"]["route_disagree_pct"]["value"] == 0.0
    assert list(line)[-1] == "compared"


def test_traced_run_writes_no_cpu_number_under_a_device_name(
        capsys, manifest_path):
    rc, out, _ = _run(capsys, manifest_path, 1, 11)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True
    # the host's and the program's own counter; nothing from a device
    assert set(line["metrics"]) == {"dispatch_ms.train",
                                    "moe_load_max_over_mean"}
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert "busy_s" not in line["device"]


def _state_unchanged(monkeypatch):
    from mxnet_tpu import optimizer

    monkeypatch.setattr(
        optimizer, "fused_sgd_mom_grouped",
        lambda keys, p, g, m, *a, **k: ({i: p[i] for i in keys},
                                        {i: m[i] for i in keys}))


def _streams_unmixed(monkeypatch):
    """The program's ``H_res`` is the identity: Sinkhorn hands back the
    unit matrix whatever it is given."""
    import jax.numpy as jnp

    from mxnet_tpu.transformer import blocks

    monkeypatch.setattr(
        blocks, "sinkhorn",
        lambda m, iters, eps: jnp.broadcast_to(jnp.eye(m.shape[-1]),
                                               m.shape) + 0.0 * m)


def _absent_experts_computed(monkeypatch):
    """The layer is told it holds other experts than its weights are."""
    from mxnet_tpu.transformer import blocks

    real = blocks.expert_ffn

    def shifted(m, lp, cfg):
        held = tuple(e + 4 for e in cfg.held_experts)
        return real(m, lp, cfg._replace(held_experts=held))

    monkeypatch.setattr(blocks, "expert_ffn", shifted)


@pytest.mark.parametrize("fault", [_state_unchanged, _streams_unmixed,
                                   _absent_experts_computed])
def test_a_broken_timed_path_is_not_correct(capsys, manifest_path,
                                            monkeypatch, fault):
    fault(monkeypatch)
    rc, out, err = _run(capsys, manifest_path, 0, 5)
    assert rc == 0
    assert json.loads(out[-1])["correct"] is False
    assert "FAILS" in err


def test_control_and_planted_faults_come_out_not_correct(capsys,
                                                         manifest_path):
    from perfbench import calibrate

    rc = calibrate.main(["--workload", CELL, "--seeds", "4", "--control",
                         "1", "--faults", "1"],
                        manifest_path=manifest_path, data_root=TOY)
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    with open(os.path.join(TOY, "workloads", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    by_what = {ln["what"]: ln for ln in lines}
    assert set(by_what) == {"program", "control", "fault_half_batch",
                            "fault_state_unchanged",
                            "fault_identity_h_res"}
    assert all(by_what["program"][k] <= v for k, v in limits.items())
    for what, line in by_what.items():
        if what != "program":
            assert any(line[k] > v for k, v in limits.items()), what
