"""``perfbench/flops_moe.py`` against hand counts at the real
configuration's sizes, and the new readers on a trace small enough to
work out by hand."""
import importlib.util
import json
import os

import pytest

from perfbench import flops_moe, program_trace as pt
from perfbench import program_trace_moe as ptm
from perfbench import spans as bench_spans
from perfbench import trace_reduce as tr
from perfbench.validate import reader_path

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "xing4-29b-a4b-ep8.json")) as f:
        return json.load(f)


def test_attention_projections_by_hand(cfg):
    # q down 3584x768, q up 768x(32x192), kv down 3584x(512+64),
    # kv up 512x(32x256), out (32x128)x3584
    assert flops_moe.attention_proj_macs(cfg) == (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584) \
        == 28409856


def test_attention_core_by_hand(cfg):
    # 32 heads, 192-wide scores and 128-wide values over 2,048 keys
    assert flops_moe.attention_core_macs(cfg, 2048) == 32 * 2048 * 320


def test_expert_layer_counts_the_held_share(cfg):
    one = 3 * 3584 * 1024
    assert flops_moe.expert_macs(cfg) == one == 11010048
    # router 64 wide, one shared expert, 4 a token of which 8/64 here
    assert flops_moe.expert_layer_macs(cfg) == pytest.approx(
        3584 * 64 + one + 4 * (8 / 64) * one)


def test_streams_by_hand(cfg):
    # the 24 map outputs from 4x3584 inputs, 4 weights in, 16 stream to
    # stream, 4 out, each 3584 wide
    assert flops_moe.stream_macs(cfg) == 14336 * 24 + 3584 * (4 + 16 + 4)


def test_step_is_three_forwards_of_two_per_multiply_add(cfg):
    t = 4096
    attended = (t + 1) / 2
    layer = 28409856 + 32 * attended * 320 + 2 * (14336 * 24 + 3584 * 24)
    expert = 3584 * 64 + 1.5 * 11010048
    token = 5 * layer + 3 * 3584 * 9216 + 4 * expert + 3584 * 16384 \
        + (2 * 3584 * 3584 + layer + expert + 3584 * 16384)
    assert flops_moe.forward_macs_per_token(cfg, attended) \
        == pytest.approx(token)
    assert flops_moe.train_step_flops(cfg, 2, t) == pytest.approx(
        6 * 2 * t * token)
    # the issue's estimate: about 1.25 GFLOP a token forward
    assert 1.1e9 < 2 * token < 1.4e9


def test_expert_roofline_work_by_hand(cfg):
    assert flops_moe.expert_layers(cfg) == 5
    assert flops_moe.expert_step_flops(cfg, 1000) == 6 * 1000 * 11010048
    # 5 layers x 8 experts x 3 matrices, 4 bytes, read twice and their
    # gradient written; six 3584-wide bf16 rows an assignment
    assert flops_moe.expert_step_bytes(cfg, 1000, steps=2) == \
        5 * 8 * 11010048 * 4 * 3 * 2 + 1000 * 3584 * 2 * 6


# ---------------------------------------------------------------------
# the readers, on a trace worked out by hand
# ---------------------------------------------------------------------
def _read(metric, ctx):
    spec = importlib.util.spec_from_file_location("m", reader_path(metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def _line(name, events):
    return {"name": name, "events": [list(e) for e in events]}


def _ctx(cfg, maps, chip=True):
    """One program run of 1000 ns in a window of 1200: 100 of routing,
    a grouped product of 300 with a child of 50 inside it, 200 of
    stream mixing, 150 of the multi-token module's experts, 100 of its
    head, 150 of a dense feed-forward."""
    ops = [
        ("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 100, 100),
        ("%custom-call.2 = bf16[8] custom-call(%b)", 200, 300),
        ("%fusion.3 = f32[8] fusion(%c), kind=kLoop", 250, 50),
        ("%fusion.4 = f32[8] fusion(%d), kind=kLoop", 500, 200),
        ("%custom-call.5 = bf16[8] custom-call(%e)", 700, 150),
        ("%fusion.6 = f32[8] fusion(%f), kind=kOutput", 850, 100),
        ("%fusion.7 = f32[8] fusion(%g), kind=kOutput", 950, 150),
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            _line("XLA Modules", [("jit_step(1)", 100, 1000)]),
            _line("XLA Ops", ops)]},
        {"name": "/host:CPU", "lines": [_line("python", [
            ("bench.window", 0, 1200)])]}]}
    spans = bench_spans.Spans()
    spans.log = [("bench.window", 5.0, 5.0 + 1200e-9)]
    return {"trace": trace, "spans": spans, "cell": {"name": "fixture"},
            "config": cfg, "busy": tr.busy_seconds(trace) if chip else None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {"steps": 1, "moe_assignments_here": 1000,
                         "moe_load_max_over_mean": 1.25},
            "_program_trace": {"maps": maps, "ring": []}}


MAPS = {"jit_step": {
    "fusion.1": "jit(step)/jvp(layer01)/mlp/moe_route/sort",
    "custom-call.2": "jit(step)/jvp(layer01)/mlp/moe_expert/ragged_dot",
    "fusion.3": "jit(step)/transpose(jvp(layer01))/mlp/moe_expert/mul",
    "fusion.4": "jit(step)/jvp(layer01)/mhc/exp",
    "custom-call.5": "jit(step)/jvp(mtp)/checkpoint/mlp/moe_expert/"
                     "ragged_dot",
    "fusion.6": "jit(step)/jvp(mtp)/head_loss/dot_general",
    "fusion.7": "jit(step)/jvp(layer00)/mlp/dot_general"}}


def test_the_new_scopes_stay_inside_the_old_classes(cfg, tmp_path,
                                                    monkeypatch):
    monkeypatch.setattr(pt, "REPORT", str(tmp_path / "r.json"))
    table = pt.scope_ms(_ctx(cfg, MAPS))
    assert table["mlp"] == pytest.approx((100 + 300 + 150 + 150) * 1e-6)
    assert table["other"] == pytest.approx(200e-6)
    assert table["head_loss"] == pytest.approx(100e-6)
    assert table["unscoped"] == 0


def test_each_new_reader_on_the_hand_made_trace(cfg):
    ctx = _ctx(cfg, MAPS)
    assert _read("moe_route_ms.train", ctx) == pytest.approx(100e-6)
    assert _read("moe_expert_ms.train", ctx) == pytest.approx(450e-6)
    assert _read("mhc_ms.train", ctx) == pytest.approx(200e-6)
    assert _read("mtp_ms.train", ctx) == pytest.approx(250e-6)
    assert _read("moe_load_max_over_mean", ctx) == 1.25
    least = max(6 * 1000 * 11010048 / 197e12,
                (5 * 8 * 11010048 * 12 + 1000 * 3584 * 12) / 819e9)
    assert _read("moe_expert_roofline.train", ctx) == pytest.approx(
        100 * least / 450e-9)


def test_the_new_readers_find_nothing_where_there_is_nothing(cfg):
    dense = {"jit_step": {k: "jit(step)/jvp(layer00)/mlp/dot_general"
                          for k in MAPS["jit_step"]}}
    for ctx in (_ctx(cfg, dense), _ctx(cfg, MAPS, chip=False),
                _ctx(cfg, None)):
        ctx["counters"].pop("moe_assignments_here")
        ctx["counters"].pop("moe_load_max_over_mean")
        if ctx["_program_trace"]["maps"] is None:
            ctx["_program_trace"].pop("maps")
            ctx["busy"] = None
        for name in ("moe_route_ms.train", "moe_expert_ms.train",
                     "mhc_ms.train", "mtp_ms.train",
                     "moe_expert_roofline.train",
                     "moe_load_max_over_mean"):
            assert _read(name, ctx) is None, name


def test_route_disagreement_counts_assignments_not_positions():
    import numpy as np

    from perfbench.drivers.train_moe_lm import _disagree_pct

    # one layer, a batch of 2 rows of 2 tokens, 2 experts a token
    program = np.array([[[0, 1], [2, 3], [4, 5], [6, 7]]])     # (L, B*T, k)
    same = program.reshape(1, 2, 2, 2).transpose(1, 0, 2, 3)   # (B, L, T, k)
    assert _disagree_pct(program, same, 2) == 0.0
    # the order within a token does not matter; one of eight differs
    other = same.copy()
    other[0, 0, 0] = [1, 0]
    other[1, 0, 1] = [6, 9]
    assert _disagree_pct(program, other, 2) == pytest.approx(12.5)
    # a reference run over the first row's first token only
    assert _disagree_pct(program, other[:1, :, :1], 2) == 0.0
