"""``drivers/serve_sarvam.py`` end to end on the CPU at a toy
configuration with every mechanism of the real one (latent attention
without a query bottleneck and with YaRN, a dense and two expert layers
of which half the experts are held, an untied head), through
``perfbench.run.main`` with only the look for a chip lifted; the cell's
control and planted faults through ``perfbench.calibrate``; the
decode-scope reader on a small made-up trace; and what the committed
cell's files say."""
import contextlib
import io
import json
import os

import pytest

from perfbench import program_trace_serve, run, validate
from perfbench.drivers import serve_lm, serve_sarvam

import toy_sarvam_manifest

TOY = toy_sarvam_manifest.TOY
ROOT = toy_sarvam_manifest.ROOT
CELL = "toy_sarvam"
NEW = ("decode_latent_attn_ms", "decode_moe_route_ms",
       "decode_moe_expert_ms", "decode_latent_attn_roofline",
       "decode_moe_expert_roofline", "moe_experts_reached_pct")


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    return toy_sarvam_manifest.write(tmp_path_factory.mktemp("toy_sarvam"))


def _run(capsys, manifest_path, trace, seed):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)],
                  manifest_path=manifest_path, data_root=TOY,
                  require_chip=False)
    captured = capsys.readouterr()
    return rc, captured.out.strip().splitlines(), captured.err


def test_toy_manifest_is_sound():
    toy = toy_sarvam_manifest.build()
    assert validate.check(toy, ROOT, TOY) == []
    listed = {m["name"] for m in toy["per_layer"]}
    assert set(NEW) <= listed
    assert "moe_load_max_over_mean" not in listed   # training's


def test_the_driver_overrides_the_four_and_the_counters_way_in():
    own = {k for k, v in vars(serve_sarvam.Driver).items()
           if callable(v)}
    assert own == {"_lm_config", "_block_bytes", "_work", "_reference_rows",
                   "token_gaps", "_compared", "check", "calibration",
                   "warm", "window", "release"}
    assert issubclass(serve_sarvam.Driver, serve_lm.Driver)


def test_untraced_run_prints_the_end_to_end_metrics(capsys, manifest_path):
    rc, out, err = _run(capsys, manifest_path, 0, 3000000019)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}
    assert set(line["compared"]) == {"token_gap", "token_gap_p90"}
    assert list(line)[-1] == "compared"
    info = "\n".join(out[:-1])
    assert "0 dropped" in info and "held experts' load max over mean" in info
    assert "info: route_disagree_pct" in info


def test_traced_run_writes_no_cpu_number_under_a_device_name(
        capsys, manifest_path):
    rc, out, _ = _run(capsys, manifest_path, 1, 11)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True
    # the host's and the program's own counters; nothing from a device
    assert set(line["metrics"]) == {
        "queue_ms_p90", "ttft_p50_ms", "ttft_p90_ms", "batch_occupancy_pct",
        "kv_live_bytes_p50", "loadgen_late_p90_ms",
        "moe_experts_reached_pct"}
    assert 0.0 < line["metrics"]["moe_experts_reached_pct"]["value"] <= 100.0


@pytest.fixture(scope="module")
def _calibrated(tmp_path_factory):
    """``perfbench.calibrate --control 1 --faults 1`` on the toy cell,
    once: its lines by what they read."""
    from perfbench import calibrate

    path = toy_sarvam_manifest.write(tmp_path_factory.mktemp("planted"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = calibrate.main(["--workload", CELL, "--seeds", "6",
                             "--control", "1", "--faults", "1"],
                            manifest_path=path, data_root=TOY)
    assert rc == 0
    return {ln["what"]: ln for ln in map(json.loads, (
        ln for ln in out.getvalue().splitlines() if ln.startswith("{")))}


@pytest.mark.parametrize("number", ["token_gap", "token_gap_p90"])
@pytest.mark.parametrize("what", ["control"] + sorted(serve_lm.FAULTS))
def test_the_control_and_every_planted_fault_fail_the_toys_numbers(
        _calibrated, what, number):
    with open(os.path.join(TOY, "workloads", CELL + ".json")) as f:
        limit = json.load(f)["limits"][number]
    assert _calibrated["program"][number] <= limit
    assert _calibrated[what][number] > limit
    # nine positions in ten lie under the percentile: never over the worst
    assert _calibrated[what]["token_gap_p90"] \
        <= _calibrated[what]["token_gap"]


# -- the decode-scope reader on a made-up trace ------------------------
def test_an_hlo_lines_signature():
    sig = program_trace_serve.signature
    assert sig("%fusion.12 = bf16[48,1,4096]{2,1,0:T(8,128)(2,1)} fusion("
               "bf16[48,1,4096]{2,1,0} %p), kind=kLoop") \
        == ("fusion.12", "bf16[48,1,4096]")
    assert sig("  ROOT %tuple.3 = (f32[4,65536]{1,0}, bf16[3073,128,576]"
               "{2,1,0}) tuple(%a, %b)") == ("tuple.3", "f32[4,65536]")
    assert sig("ENTRY %main.1 (p: f32[2]) -> f32[2] {") is None


def test_decode_scope_ms_picks_each_executions_own_cell(monkeypatch):
    """Two plan cells whose instructions share their NAMES and differ in
    shape and in scope: an execution is classed by the cell whose shapes
    it shows."""
    ms = 1e6
    small = ({("fusion.1", "bf16[2,128,576]"), ("fusion.2", "bf16[2,4096]")},
             {"fusion.1": "jit(decode_fn)/layers/attn/gather",
              "fusion.2": "jit(decode_fn)/layers/mlp/moe_expert/dot"})
    large = ({("fusion.1", "bf16[4,256,576]"), ("fusion.2", "bf16[4,4096]")},
             {"fusion.1": "jit(decode_fn)/layers/mlp/moe_route/sort",
              "fusion.2": "jit(decode_fn)/layers/attn/gather"})
    monkeypatch.setattr(program_trace_serve, "plan_cells",
                        lambda: [small, large])
    ops = [["%fusion.1 = bf16[2,128,576]{2,1,0} fusion()", 1.0 * ms, .5 * ms],
           ["%fusion.2 = bf16[2,4096]{1,0} fusion()", 1.5 * ms, .25 * ms],
           ["%fusion.1 = bf16[4,256,576]{2,1,0} fusion()", 4 * ms, 1 * ms],
           ["%fusion.2 = bf16[4,4096]{1,0} fusion()", 5 * ms, .5 * ms],
           # the grouped product, as the chip's compiler names it: no
           # scope of the program's on it
           ["%ragged-dot-none.3 = bf16[4,4096]{1,0} custom-call()", 5.5 * ms,
            .125 * ms],
           # a prefill's operations are none of a decode program's
           ["%fusion.1 = bf16[2,128,576]{2,1,0} fusion()", 7 * ms, 1 * ms]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_decode_fn(11)", 1 * ms, 1 * ms],
                ["jit_decode_fn(22)", 4 * ms, 2 * ms],
                ["jit_prefill_fn(33)", 7 * ms, 1 * ms]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 0.0, 10 * ms]]}]}]}
    ctx = {"trace": trace, "busy": {"busy_s": 1.0, "window_s": 1.0},
           "peaks": {"bf16_flops_per_s": 2.0e9, "hbm_bytes_per_s": 1.0e9},
           "counters": {"latent_attn_bytes": 250000.0,
                        "latent_attn_flops": 1.0e5,
                        "moe_expert_bytes": 50000.0,
                        "moe_expert_flops": 4.0e5}}
    table = program_trace_serve.decode_scope_ms(ctx)
    assert table["runs"] == 2
    # attn: 0.5 ms in the small cell's run + 0.5 ms in the large one's
    assert table["attn"] == pytest.approx((0.5 + 0.5) / 2)
    assert table["moe_expert"] == pytest.approx((0.25 + 0.125) / 2)
    assert table["moe_route"] == pytest.approx(1.0 / 2)
    assert run._reader("decode_latent_attn_ms")(ctx) \
        == pytest.approx(0.5)
    assert run._reader("decode_moe_route_ms")(ctx) == pytest.approx(0.5)
    # bytes bound attention: 0.25 ms least over 1 ms; operations bound
    # the experts: 0.2 ms over 0.375 ms
    assert run._reader("decode_latent_attn_roofline")(ctx) \
        == pytest.approx(25.0)
    assert run._reader("decode_moe_expert_roofline")(ctx) \
        == pytest.approx(100.0 * 0.2 / 0.375)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric,
                                                           monkeypatch):
    monkeypatch.setattr(program_trace_serve, "plan_cells", lambda: None)
    ctx = {"trace": None, "busy": None, "peaks": None, "counters": {}}
    assert run._reader(metric)(ctx) is None


# -- the committed cell ------------------------------------------------
def test_the_cells_file_names_its_traffic_its_sweep_and_its_limit():
    with open(os.path.join(ROOT, "perfbench", "workloads",
                           "sarvam_serve_reason.json")) as f:
        cell = json.load(f)
    assert (cell["prompt"]["median"], cell["prompt"]["sigma"],
            cell["prompt"]["min"], cell["prompt"]["max"]) \
        == (3584, 0.5, 512, 5120)
    assert (cell["output"]["median"], cell["output"]["sigma"],
            cell["output"]["min"], cell["output"]["max"]) \
        == (1280, 0.6, 256, 3072)
    assert (cell["slots"], cell["block_tokens"], cell["warm_seconds"],
            cell["check_requests"]) == (48, 128, 45, 24)
    assert cell["slots"] * 8192 // cell["block_tokens"] + 1 == 3073
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert cell["trace_seconds"] == json.load(f)["run_seconds"]
    with open(os.path.join(ROOT, "perfbench", "workloads",
                           "lm_serve_chat.json")) as f:
        chat = json.load(f)
    for key in ("queue_max", "deadline_ms", "control", "faults"):
        assert cell[key] == chat[key]
    # the knee the sweep found is stated where it is explained, with the
    # grid; the rate is four fifths of it
    knee = float(cell["assumed"]["knee"].split()[0])
    assert cell["rate"] == pytest.approx(0.8 * knee, rel=1e-9)
    assert cell["sweep"]["knee"] == knee
    assert knee in cell["sweep"]["rates"]
    assert "as recalled" in cell["source"]
    assert cell["limits"]["token_gap"] > 0 and cell["limits_why"]


def test_the_configuration_holds_the_published_widths():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "sarvam-105b-ep4.json")) as f:
        cfg = json.load(f)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_width"],
            cfg["num_shared_experts"], cfg["routed_scaling_factor"]) \
        == (4096, 64, 512, 128, 64, 128, 16384, 2048, 8, 128, 1, 2.5)
    assert (cfg["rope_scaling"]["factor"],
            cfg["rope_scaling"]["original_max_position_embeddings"]) \
        == (40, 4096)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 262144}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 32, 65536)
    assert cfg["held_experts"] == list(range(32))
    for key in ("deployment", "why_reduced", "assumed"):
        assert cfg[key]
