"""``perfbench/program_trace_engine.py`` and the five ``idle_*_ms``
readers, on a trace and a ring built by hand: idle time of the chip
planted under each phase of the engine, either clock shifted by what
causality bounds, and placings no lag can make causal, which give no
number."""
import importlib.util
import json
import os

import pytest

from perfbench import program_trace_engine as pte
from perfbench import spans as bench_spans
from perfbench import trace_reduce as tr
from perfbench import validate
from perfbench.validate import reader_path

from mxnet_tpu.profiler import Span

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
PHASES = ("prepare", "launch", "readback", "stream", "loop")
METRICS = ["idle_%s_ms" % p for p in PHASES]


def _read(metric, ctx):
    spec = importlib.util.spec_from_file_location("m", reader_path(metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def _line(name, events):
    return {"name": name, "events": [list(e) for e in events]}


def _host(ns):
    """A time on the profiler's clock (ns) on the program's: seconds,
    5 s behind."""
    return 5.0 + ns * 1e-9


def _hand_made(log_shift_ns=0.0, chip_shift_ns=0.0, run=(2000, 4000)):
    """A window of 10,000 ns.  The engine's thread: idle to 1,000, then
    one worked pass to 9,000: prepare 1,000-2,000, the tick 2,000-6,000
    (its launch 2,000-2,200, its readback 2,200-5,800), the stream
    6,000-8,000, the loop's own time 8,000-9,000.  The chip: the decode
    run 2,000-4,000 (two operations, 100 ns apart), which starts as the
    tick does (the least lag causality allows is the true one), and
    short operations that leave idle time under each phase:

        no work   100-900       800
        prepare   1,200-2,000   800
        launch    5,800-6,000   200 (the tick's own)
        readback  3,000-3,100   100 (inside the run) + 4,000-5,800 1,800
        stream    6,000-6,100   100 + 6,200-7,900 1,700
        loop      8,100-8,900   800
        (outside) 9,100-10,000  900

    ``log_shift_ns`` moves the harness's reading of the window's start,
    and so the ring on the trace; ``chip_shift_ns`` moves every event
    of the chip."""
    a, b = run
    ops = [("%fusion.1 = f32[8] fusion(%x)", s + chip_shift_ns, e - s)
           for s, e in ((0, 100), (900, 1200), (a, 3000), (3100, b),
                        (6100, 6200), (7900, 8100), (8900, 9100))]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            _line("XLA Modules", [("jit_decode_fn(7)", a + chip_shift_ns,
                                   b - a)]),
            _line("XLA Ops", ops)]},
        {"name": "/host:CPU", "lines": [_line("python", [
            ("bench.window", 0, 10000)])]}]}
    log = [("bench.window", _host(log_shift_ns), _host(10000))]
    ring = [
        Span("mx.serve.idle", _host(0), _host(1000), 3, 0, None),
        Span("mx.serve.loop", _host(1000), _host(9000), 3, 0, None),
        Span("mx.engine.prepare", _host(1000), _host(2000), 3, 1, None),
        Span("mx.tick", _host(2000), _host(6000), 3, 1,
             {"live": 1, "slots": 4}),
        Span("mx.step.launch", _host(2000), _host(2200), 3, 2, None),
        Span("mx.tick.readback", _host(2200), _host(5800), 3, 2, None),
        Span("mx.engine.stream", _host(6000), _host(8000), 3, 1, None),
        # another thread's record: none of the engine's phases
        Span("mx.step", _host(100), _host(9900), 1, 0, None),
    ]
    return trace, log, ring


def _ctx(trace, log, ring, chip=True):
    spans = bench_spans.Spans()
    spans.log = [tuple(e) for e in log]
    return {"trace": trace, "spans": spans, "cell": {"name": "fixture"},
            "busy": tr.busy_seconds(trace) if chip else None,
            "counters": {}, "_program_trace": {"ring": ring}}


@pytest.fixture(autouse=True)
def _report(tmp_path, monkeypatch):
    monkeypatch.setattr(pte, "REPORT", str(tmp_path / "engine_idle.json"))


# each phase's gap, in ms, over the one tick in the window
BY_HAND = {"idle_prepare_ms": 800e-6, "idle_launch_ms": 200e-6,
           "idle_readback_ms": 1900e-6, "idle_stream_ms": 1800e-6,
           "idle_loop_ms": 800e-6}


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_reads_its_planted_gap_a_tick(metric):
    assert _read(metric, _ctx(*_hand_made())) == pytest.approx(
        BY_HAND[metric])


def test_the_report_sums_to_the_chips_idle_time():
    ctx = _ctx(*_hand_made())
    report = pte.engine_idle(ctx)
    with open(pte.REPORT) as f:
        assert json.load(f) == json.loads(json.dumps(report))
    assert report["inside_share"] == 1.0 and report["decode_runs"] == 1
    # the run may lag until it ends as its readback does: 1,800 ns
    assert report["ticks"] == 1
    assert report["lag_ns"] == {"least": 0.0, "most": 1800.0}
    seconds = report["seconds"]
    assert seconds["no work"] == pytest.approx(800e-9)
    assert seconds["(outside)"] == pytest.approx(900e-9)
    assert report["sum_s"] == pytest.approx(report["device_idle_s"]) \
        == pytest.approx(7200e-9)
    # at the most lag the ring lies 1,800 ns earlier on the chip: the
    # dispatch's share grows and the readback's shrinks
    most = report["seconds_at_most_lag"]
    assert {k: round(v * 1e9) for k, v in most.items()} == {
        "prepare": 100, "launch": 400, "readback": 1400, "stream": 1900,
        "loop": 1000, "no work": 0, "(outside)": 2400}
    assert report["idle_ms_at_most_lag"]["launch"] == pytest.approx(400e-6)


def test_own_time_is_the_record_less_what_lies_inside_it():
    _, _, ring = _hand_made()
    own = {}
    for a, b, phase in pte.own_time(ring):
        own[phase] = own.get(phase, 0.0) + round((b - a) * 1e9)
    # the launch under the tick is no phase: it is the tick's own time
    assert own == {"no work": 1000, "loop": 1000, "prepare": 1000,
                   "launch": 400, "readback": 3600, "stream": 2000}


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("log_shift_ns, chip_shift_ns", [
    (-700, 0), (0, -900), (-1500, -400)])
def test_the_chip_is_placed_by_when_its_runs_start(metric, log_shift_ns,
                                                   chip_shift_ns):
    # the profiler's chip clock lies up to a millisecond from its host
    # clock: a run that starts before the tick that launched it is
    # moved until it starts with it, so a shift of either clock that
    # way reads the same numbers
    ctx = _ctx(*_hand_made(log_shift_ns, chip_shift_ns))
    assert _read(metric, ctx) == pytest.approx(BY_HAND[metric])
    assert pte.engine_idle(ctx)["lag_ns"]["least"] == pytest.approx(
        -chip_shift_ns - log_shift_ns)


@pytest.mark.parametrize("metric", METRICS)
def test_a_misplaced_ring_gives_no_number(metric):
    # the harness's reading of the window 10 ms early: the ring lands
    # 10 ms late, more than the chip's clock is ever taken to lag
    ctx = _ctx(*_hand_made(log_shift_ns=-10e6))
    assert _read(metric, ctx) is None
    assert pte.engine_idle(ctx)["lag_ns"]["least"] == pytest.approx(10e6)


@pytest.mark.parametrize("metric", METRICS)
def test_a_chip_late_past_the_readbacks_tail_gives_no_number(metric):
    # the chip 2,500 ns late on the trace (or the ring as early): the run
    # now ends after the readback that read it, which no lag of the chip
    # behind the host can mend
    ctx = _ctx(*_hand_made(chip_shift_ns=2500))
    assert _read(metric, ctx) is None
    assert pte.engine_idle(ctx)["inside_share"] == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_a_run_its_tick_cannot_hold_gives_no_number(metric):
    # a decode run longer than the tick that launched it: no placing of
    # the chip puts it inside
    ctx = _ctx(*_hand_made(run=(2000, 6500)))
    assert _read(metric, ctx) is None
    assert pte.engine_idle(ctx)["inside_share"] == 0.0


def test_a_run_pairs_with_the_call_that_read_it():
    # the second call's dispatch holds the host past the next call's
    # start: its run starts nearer the third call's start, and is still
    # the second's, the first whose readback ends after it
    called = [(0, 300), (400, 1600), (1650, 2000)]
    runs = [(-30, 200), (1300, 1400), (1700, 1900)]
    assert [c for _, c in pte.paired(runs, called)] == called
    assert pte.lag_bounds(runs, called) == (30, 100)
    assert pte.inside_share(runs, called, 30) == 1.0
    assert pte.inside_share(runs, called, 0) == pytest.approx(2 / 3)


@pytest.mark.parametrize("metric", METRICS)
def test_no_number_without_a_chip_or_the_engines_records(metric):
    trace, log, ring = _hand_made()
    assert _read(metric, _ctx(trace, log, ring, chip=False)) is None
    older = [s for s in ring if s.name in ("mx.tick", "mx.step",
                                           "mx.step.launch")]
    assert _read(metric, _ctx(trace, log, older)) is None
    assert _read(metric, _ctx(trace, log, None)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_manifest_lists_the_latent_served_cell(metric):
    # the latent cell alone: ``test_manifest.py`` counts the serving
    # metrics that list ``lm_serve_chat`` (ten), so listing that cell
    # too is for a change that may edit that count
    entry = next(m for m in validate.load(ROOT)["per_layer"]
                 if m["name"] == metric)
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "serving_scheduler", "moves": "tpot_p95_ms",
                     "workloads": ["sarvam_serve_reason"]}
