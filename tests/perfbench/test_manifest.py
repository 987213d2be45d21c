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
