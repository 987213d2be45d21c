"""``perfbench/program_trace.py`` and the readers that stand on it, on a
trace cut from a traced chip run of ``lm_train_s2048``
(``fixtures/v5e_scoped_trace.json``: two steps of PR 26's run in the
structure ``trace_reduce`` documents, with the program's map and the
spans of both clocks as the run had them, the window drawn round the
two steps) and on cases small enough to work out by hand."""
import importlib.util
import json
import os

import pytest

from perfbench import program_trace as pt
from perfbench import spans as bench_spans
from perfbench import trace_reduce as tr
from perfbench.validate import reader_path

from mxnet_tpu.profiler import Span

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 2


def _read(metric, ctx):
    spec = importlib.util.spec_from_file_location("m", reader_path(metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def _ctx(trace, log, ring, maps, steps, chip=True):
    """What ``perfbench.run`` hands a reader, with what the program
    would hand out (its map, its ring) put where ``program_trace``
    keeps them once computed."""
    spans = bench_spans.Spans()
    spans.log = [tuple(e) for e in log]
    return {"trace": trace, "spans": spans, "cell": {"name": "fixture"},
            "busy": tr.busy_seconds(trace) if chip else None,
            "counters": {"steps": steps},
            "_program_trace": {"maps": maps, "ring": ring}}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "v5e_scoped_trace.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(recorded, tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "REPORT", str(tmp_path / "report.json"))
    return _ctx(recorded, recorded["log"],
                [Span(*s) for s in recorded["ring"]], recorded["maps"],
                STEPS)


# ---------------------------------------------------------------------
# by hand
# ---------------------------------------------------------------------
def _line(name, events):
    return {"name": name, "events": [list(e) for e in events]}


def _hand_made():
    """One program run of 1000 ns inside a window of 1200: a ``while``
    of 600 (attn) with two children of 100 and 150 (attn) and one of 50
    the map does not know; a fusion of 200 (mlp); a copy of 100 with no
    scope that starts 40 before the fusion ends; idle for the rest."""
    ops = [
        ("%while.1 = (s32[]) while(%t), body=%b", 100, 600),
        ("%fusion.2 = f32[8] fusion(%x), kind=kLoop", 150, 100),
        ("%fusion.3 = f32[8] fusion(%x), kind=kLoop", 300, 150),
        ("%fusion.9 = f32[8] fusion(%x), kind=kLoop", 500, 50),
        ("%fusion.4 = f32[8] fusion(%y), kind=kOutput", 700, 200),
        ("%copy.5 = f32[8] copy(%z)", 860, 100),
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            _line("XLA Modules", [("jit_step(123)", 100, 1000)]),
            _line("XLA Ops", ops)]},
        {"name": "/host:CPU", "lines": [_line("python", [
            ("bench.window", 0, 1200), ("bench.step", 10, 50)])]}]}
    maps = {"jit_step": {
        "while.1": "jit(step)/jvp(layer00)/attn/while",
        "fusion.2": "jit(step)/jvp(layer00)/attn/while/body/mul",
        "fusion.3": "jit(step)/transpose(jvp(layer00))/attn/while/body/dot_general",
        "fusion.4": "jit(step)/jvp(layer00)/mlp/dot_general",
        "copy.5": ""}}
    # the harness's clock: seconds, 5 s behind the profiler's
    log = [("bench.window", 5.0, 5.0 + 1200e-9),
           ("bench.step", 5.0 + 10e-9, 5.0 + 60e-9)]
    ring = [Span("mx.step", 5.0 + 12e-9, 5.0 + 58e-9, 0, 0, None),
            Span("mx.step.feed", 5.0 + 14e-9, 5.0 + 24e-9, 0, 1, None),
            Span("mx.step.launch", 5.0 + 26e-9, 5.0 + 56e-9, 0, 1, None),
            Span("mx.compile", 5.0 + 26e-9, 5.0 + 56e-9, 0, 1,
                 {"step": "s"})]
    return trace, log, ring, maps


def test_a_while_and_its_children_are_counted_once(tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "REPORT", str(tmp_path / "r.json"))
    ctx = _ctx(*_hand_made(), steps=1)
    table = pt.scope_ms(ctx)
    # attn: the while's 600 less the unknown child's 50; mlp: 200 less
    # the 40 the later-started copy covers; unscoped: 50 + 100
    assert table == pytest.approx({
        "attn": 550e-6, "mlp": 160e-6, "unscoped": 150e-6,
        "attn_proj": 0, "head_loss": 0, "conv": 0, "bn_act": 0,
        "optimizer": 0, "other": 0})
    assert sum(table.values()) == pytest.approx(
        1e3 * ctx["busy"]["busy_s"]) == pytest.approx(860e-6)
    with open(pt.REPORT) as f:
        report = json.load(f)
    assert [k.split(" ")[0] for k, _ in report["largest_unscoped_ms"]] \
        == ["%copy.5", "%fusion.9"]


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(layer03)/attn/closed_call/while", "attn"),
    ("jit(step)/transpose(jvp(layer03))/jvp(layer03)/checkpoint/"
     "rematted_computation/attn_proj/dot_general", "attn_proj"),
    ("jit(step)/transpose(jvp(layer00))/mlp/jit(gelu)/tanh", "mlp"),
    ("jit(step)/jvp(head_loss)/reduce_max", "head_loss"),
    ("jit(step)/optimizer/concatenate", "optimizer"),
    ("jit(step)/jvp(net)/net_stage1/net_stage1_conv0/Convolution/"
     "jit(<unknown>)/conv_general_dilated", "conv"),
    ("jit(step)/transpose(jvp(net))/net_bn0/BatchNorm/jit(<unknown>)/"
     "mul", "bn_act"),
    ("jit(step)/jvp(net)/net_relu0/Activation/max", "bn_act"),
    ("jit(step)/jvp(net)/net_stage1/_binary_add/add", "bn_act"),
    ("jit(step)/jvp(net)/net_pool0/Pooling/reduce_window_max", "bn_act"),
    ("jit(step)/jvp(net)/net_dense0/FullyConnected/dot_general", "other"),
    ("jit(step)/jvp(layer00)/norm/rsqrt", "other"),
    ("jit(step)/jvp(layer00)/add", "other"),
    ("jit(step)/mxbkt003/psum", "other"),
    ("jit(step)/cast/convert_element_type", "other"),
    ("jit(step)/jvp(net)/net_stage1/mul", "unscoped"),
    ("jit(step)/transpose/transpose", "other"),   # the OPERATOR transpose
    ("jit(step)/transpose(jvp())/transpose", "unscoped"),
    ("", "unscoped"),
])
def test_the_class_is_the_last_vocabulary_name_on_the_path(op_name, want):
    assert pt.scope_class(op_name, pt.operators()) == want


def test_clock_offset_and_the_spans_own_time_by_hand():
    ctx = _ctx(*_hand_made(), steps=1)
    assert pt.clock_offset(ctx) == pytest.approx(
        {"offset_ns": -5e9, "pairs": 1, "scatter_ns": 0.0})
    own = sorted((n, round((b - a) * 1e9)) for n, a, b in
                 pt.self_intervals(ctx["_program_trace"]["ring"]))
    # mx.step's own time: 46 less feed's 10 and launch's 30, in pieces
    assert own == [("mx.compile", 30), ("mx.step", 2), ("mx.step", 2),
                   ("mx.step", 2), ("mx.step.feed", 10),
                   ("mx.step.launch", 30)]
    # the chip is idle 0-100 and 960-1200: the first gap lies under the
    # launch (26-56 of it) more than under anything else of the program
    # (a compile covers its launch whole, and is named first)
    assert pt.idle_by_program_span(ctx) == [
        ["(outside)", pytest.approx(240e-9)],
        ["mx.compile", pytest.approx(100e-9)]]


# ---------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------
HAND = {"attn_ms.train": 550e-6, "mlp_ms.train": 160e-6,
        "unscoped_ms.train": 150e-6, "attn_proj_ms.train": 0.0,
        "head_loss_ms.train": 0.0, "conv_ms.train": 0.0,
        "bn_act_ms.train": 0.0, "optimizer_ms.train": 0.0,
        "step_call_ms.train": 46e-6, "step_launch_ms.train": 30e-6,
        "compiles_in_window": 1}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_gives_the_number_worked_out_by_hand(metric, tmp_path,
                                                    monkeypatch):
    monkeypatch.setattr(pt, "REPORT", str(tmp_path / "r.json"))
    assert _read(metric, _ctx(*_hand_made(), steps=1)) == pytest.approx(
        HAND[metric])


@pytest.mark.parametrize("metric", sorted(HAND) + ["batch_occupancy_pct"])
def test_reader_gives_none_on_a_trace_with_no_chip(metric):
    trace, log, ring, maps = _hand_made()
    trace["planes"] = trace["planes"][1:]
    assert _read(metric, _ctx(trace, log, ring, maps, steps=1,
                              chip=False)) is None


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_gives_none_for_a_program_without_map_or_ring(metric):
    trace, log, _, _ = _hand_made()
    ctx = _ctx(trace, log, None, None, steps=1)
    assert _read(metric, ctx) is None


def test_batch_occupancy_weighs_live_slots_by_tick_time():
    trace, log, _, maps = _hand_made()
    ring = [Span("mx.tick", 5.0 + 100e-9, 5.0 + 200e-9, 1, 0,
                 {"live": 4, "slots": 8}),
            Span("mx.tick", 5.0 + 300e-9, 5.0 + 600e-9, 1, 0,
                 {"live": 8, "slots": 8}),
            Span("mx.tick", 6.0, 6.1, 1, 0, {"live": 1, "slots": 8})]
    # (100 x 4 + 300 x 8) / (400 x 8); the third tick starts after the
    # window
    assert _read("batch_occupancy_pct",
                 _ctx(trace, log, ring, maps, 1)) == pytest.approx(87.5)


# ---------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------
def _painted(ctx):
    """The scope table another way: the window's instants as the cells
    between all starts and ends, painted over by each operation in
    order of its start, so that the latest-started stays on top."""
    import numpy as np

    trace, maps = ctx["trace"], ctx["_program_trace"]["maps"]
    win, plane = tr.window(trace), tr.device_planes(trace)[0]
    program = maps["jit_step_body"]
    ops = pt.operators()
    events = sorted(
        (max(s, win[0]), -min(s + d, win[1]),
         pt.CLASSES.index(pt.scope_class(
             program.get(pt.instruction(n), ""), ops)))
        for n, s, d in tr._events(plane, tr.OPS_LINE))
    edges = np.unique([t for a, b, _ in events for t in (a, -b)])
    cells = np.full(len(edges) - 1, -1)
    for a, b, cls in events:
        cells[np.searchsorted(edges, a):np.searchsorted(edges, -b)] = cls
    widths = np.diff(edges)
    return {c: float(widths[cells == i].sum()) / 1e6 / STEPS
            for i, c in enumerate(pt.CLASSES)}


def test_classes_sum_to_the_device_busy_time_and_agree_with_painting(ctx):
    table = pt.scope_ms(ctx)
    assert sum(table.values()) == pytest.approx(
        1e3 * ctx["busy"]["busy_s"] / STEPS, rel=1e-9)
    assert table == pytest.approx(_painted(ctx), rel=1e-9, abs=1e-9)
    assert table["conv"] == table["bn_act"] == 0.0
    assert table["unscoped"] < 0.10 * sum(table.values())


# the two recorded steps; the two host means by hand from the ring:
# (1.10552 + 1.214699) / 2 and (0.6512 + 0.73244) / 2 ms
RECORDED = {
    "attn_ms.train": 129.710849, "attn_proj_ms.train": 56.786042,
    "mlp_ms.train": 66.059092, "head_loss_ms.train": 36.5983635,
    "optimizer_ms.train": 71.3056645, "unscoped_ms.train": 0.0001115,
    "conv_ms.train": 0.0, "bn_act_ms.train": 0.0,
    "step_call_ms.train": 1.1601095, "step_launch_ms.train": 0.69182,
    "compiles_in_window": 0,
}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_reader_on_the_recorded_trace(ctx, metric):
    assert _read(metric, ctx) == pytest.approx(RECORDED[metric], rel=1e-6)


def test_the_two_clocks_of_the_recorded_trace_lie_50us_apart_at_most(ctx):
    offset = pt.clock_offset(ctx)
    assert offset["pairs"] == STEPS and offset["scatter_ns"] < 50e3
    # every span of the program lies inside its bench.step once shifted
    shift = offset["offset_ns"]
    steps = sorted((s, s + d) for n, s, d in tr.host_spans(ctx["trace"])
                   if n == "bench.step")
    calls = sorted((s.t0 * 1e9 + shift, s.t1 * 1e9 + shift)
                   for s in ctx["_program_trace"]["ring"]
                   if s.name == "mx.step")
    assert len(calls) == STEPS
    for (a, b), (c, d) in zip(steps, calls):
        assert a <= c <= d <= b


def test_idle_gaps_of_the_recorded_trace_lie_under_the_next_steps_feed(ctx):
    table = pt.idle_by_program_span(ctx)
    assert [name for name, _ in table[:2]] == ["mx.step.feed", "(outside)"]
    idle = ctx["busy"]["window_s"] - ctx["busy"]["busy_s"]
    assert sum(v for _, v in table) == pytest.approx(idle, rel=1e-6)
