"""BENCHMARK.json and its data files pass the validator, and the
validator refuses what the driver refuses (PR 22: a layer named in
plain words)."""
import copy
import json
import os

import pytest

from perfbench import validate

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
DATA = os.path.join(ROOT, "perfbench")


@pytest.fixture(scope="module")
def manifest():
    return validate.load(ROOT)


def test_committed_manifest_is_sound(manifest):
    assert validate.check(manifest, ROOT, DATA) == []


def test_toy_manifest_is_sound():
    import toy_manifest

    toy = toy_manifest.build()
    assert validate.check(toy, ROOT, toy_manifest.TOY) == []
    # it covers every driver, the serving one too
    assert len(toy["workloads"]) == 3


SERVING = {"tpot_p95_ms", "serve_tokens_per_s", "mfu.serve",
           "device_idle_pct.serve", "prefill_roofline", "decode_roofline",
           "queue_ms_p90", "ttft_p90_ms", "batch_occupancy_pct",
           "kv_live_bytes_p50", "loadgen_late_p90_ms"}


def test_the_serving_cell_and_its_entries(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert len(cells) == 4 and all(w["chips"] == 1 for w in cells.values())
    assert cells["lm_serve_chat"]["config"] == "dense-2048x24"
    assert cells["lm_serve_chat"]["traffic"] == "azure_conv_open_loop"
    twin = {c["name"]: c for c in manifest["configs"]}
    assert twin["dense-2048x24"]["source"] == twin["dense-2048x6"]["source"]
    assert twin["dense-2048x24"]["reduced"] == []
    mine = {m["name"]: m for key in ("end_to_end", "per_layer")
            for m in manifest[key]
            if m.get("workloads") == ["lm_serve_chat"]}
    assert SERVING <= set(mine)
    # a time to first token is held somewhere: end to end or by layer
    assert "ttft_p50_ms" in mine
    for name, m in mine.items():
        if "bound" not in m:
            assert os.path.isfile(validate.reader_path(name)), name
            assert m["moves"] in ("tpot_p95_ms", "serve_tokens_per_s")
    # a queue or a wait for the first token cannot move the gap between
    # two tokens; where they grow, the server is falling behind
    for name in ("loadgen_late_p90_ms", "queue_ms_p90", "ttft_p50_ms",
                 "ttft_p90_ms"):
        assert mine[name]["moves"] == "serve_tokens_per_s"
    # the training metrics keep their cells
    step = next(m for m in manifest["end_to_end"]
                if m["name"] == "train_step_ms")
    assert "lm_serve_chat" not in step["workloads"]


def test_the_serving_cells_file_names_its_source_and_its_sizes():
    with open(os.path.join(DATA, "workloads", "lm_serve_chat.json")) as f:
        cell = json.load(f)
    assert "AzureLLMInferenceTrace_conv" in cell["source"]
    assert "2311.18677" in cell["source"]
    for key in ("shape", "prompt.sigma", "output.sigma", "arrivals",
                "warm_seconds", "knee"):
        assert cell["assumed"][key]
    # PR 34's review: a traced window of 15 s held 9 requests, one of
    # 30 s the quieter part of the schedule; the traced window is the
    # timed one
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert cell["trace_seconds"] == json.load(f)["run_seconds"]
    assert cell["warm_seconds"] == 20
    assert isinstance(cell["rate"], float) and cell["rate"] > 0
    # the knee the sweep found is stated where it is explained, and
    # read by no code: its text begins with the number
    knee = float(cell["assumed"]["knee"].split()[0])
    assert cell["rate"] == pytest.approx(0.8 * knee, rel=1e-9)
    from perfbench.drivers import serve_lm
    assert cell["faults"] and set(cell["faults"]) <= set(serve_lm.FAULTS)
    assert (cell["prompt"]["median"], cell["prompt"]["min"],
            cell["prompt"]["max"]) == (1020, 64, 1536)
    assert (cell["output"]["median"], cell["output"]["min"],
            cell["output"]["max"]) == (129, 16, 512)
    with open(os.path.join(DATA, "configs", "dense-2048x24.json")) as f:
        cfg = json.load(f)
    assert cell["prompt"]["max"] + cell["output"]["max"] \
        == cfg["max_position_embeddings"] == 2048
    # every slot can hold a whole context
    assert cell["slots"] * 2048 // cell["block_tokens"] + 1 == 161


def test_no_cell_config_or_metric_is_named_in_run_py(manifest):
    with open(os.path.join(DATA, "run.py")) as f:
        source = f.read()
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in manifest[key]]
    named = [n for n in names if n != "setup_s" and n in source]
    assert named == []


def _break(manifest, path, value):
    broken = copy.deepcopy(manifest)
    node = broken
    for key in path[:-1]:
        node = node[key]
    if value is None:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return broken


@pytest.mark.parametrize("path, value, says", [
    (("per_layer", 0, "layer"), "training loop (parallel/dp.py)", "layer"),
    (("per_layer", 0, "name"), "dispatch ms", "letters"),
    (("workloads", 0, "name"), "a/b", "letters"),
    (("configs", 0, "name"), "-x", "letters"),
    (("end_to_end", 0, "unit"), "tokens per second", "unit"),
    (("configs", 0, "source"), "x" * 201, "200"),
    (("per_layer", 0, "moves"), "no_such_metric", "moves"),
    (("per_layer", 0, "why"), "a reason", "not allowed"),
    (("end_to_end", 0, "bound"), 0.2, "bound"),
    (("end_to_end", 0, "source"), "program_counter", "host_clock"),
    (("workloads", 0, "chips"), 2, "chips"),
    (("workloads", 0, "config"), "no_such_config", "unknown config"),
    (("configs", 0, "file"), "mxnet_tpu/env.py", "under paths"),
    (("configs", 0, "reduced"), ["hidden_size"], "width"),
    (("run_seconds",), 52, "run_seconds"),
    (("command",), ["python", "/root/x.py"], "leaves"),
    (("end_to_end", "setup_s"), None, "setup_s"),
])
def test_validator_refuses(manifest, path, value, says):
    if path == ("end_to_end", "setup_s"):
        path = ("end_to_end", [m["name"] for m in
                               manifest["end_to_end"]].index("setup_s"))
    faults = validate.check(_break(manifest, path, value), ROOT, DATA)
    assert faults and any(says in f for f in faults), faults


def test_per_layer_metric_needs_its_cells_to_report_what_it_moves(manifest):
    broken = copy.deepcopy(manifest)
    moved = broken["per_layer"][0]["moves"]
    for m in broken["end_to_end"]:
        if m["name"] == moved:
            m["workloads"] = ["no_such_cell"]
    faults = validate.check(broken, ROOT, DATA)
    assert any("does not report" in f for f in faults), faults


def test_missing_workload_file_is_a_fault(manifest, tmp_path):
    faults = validate.check(manifest, ROOT, str(tmp_path))
    assert any("no file" in f for f in faults), faults
