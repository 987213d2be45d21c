"""The yardstick's arithmetic against hand counts: FLOP and byte
functions, the trace reduction on a small recorded trace, the load
generator's schedule, the weights from the seed."""
import json
import math
import os

import numpy as np
import pytest

from perfbench import flops, loadgen, trace_reduce, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))


def _config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        return json.load(f)


# -- FLOPs and bytes ---------------------------------------------------
def test_resnet50_forward_is_two_per_multiply_add():
    cfg = _config("resnet50_v1.json")
    # by hand, stage by stage (multiply-adds): the stem, then for each
    # stage the first unit (with its projection) and the others
    stem = 3 * 64 * 49 * 112 * 112
    total = stem
    size, c_in = 56, 64
    for units, c_out in zip((3, 4, 6, 3), (256, 512, 1024, 2048)):
        mid = c_out // 4
        if c_out != 256:
            size //= 2            # the stride sits on the first 1x1
        px = size * size
        first = px * (c_in * mid + 9 * mid * mid + mid * c_out
                      + c_in * c_out)
        other = px * (c_out * mid + 9 * mid * mid + mid * c_out)
        total += first + (units - 1) * other
        c_in = c_out
    total += 2048 * 1000
    assert flops.resnet_forward_flops(cfg) == 2.0 * total
    # He et al., table 1: 3.8e9 multiply-adds for the 50-layer net
    assert 3.8e9 < total < 3.9e9
    assert flops.resnet_train_step_flops(cfg, 128) == 3 * 128 * 2.0 * total
    # the old records' alg_step_gflops 392.6 at batch 32 is 3 x 4.09 G
    # multiply-adds an image counted as FLOPs: half of the count here
    assert 392.6e9 / 32 / 3 == pytest.approx(4.09e9, rel=0.01)


def test_one_transformer_layer_by_hand():
    cfg = _config("dense-2048x6.json")
    d, f = 2048, 8192
    tokens, attended = 8192, 1024.5
    by_hand = 2 * tokens * (3 * d * d + d * d + 2 * d * f) \
        + 4 * tokens * attended * d
    assert flops.transformer_layer_forward_flops(cfg, tokens, attended) \
        == by_hand
    params = 50304 * d + 6 * (4 * d * d + 2 * d * f + 2 * d) + d
    assert flops.transformer_params(cfg) == params
    assert 404e6 < params < 407e6
    step = flops.transformer_train_step_flops(cfg, 4, 2048)
    head = 2 * tokens * d * 50304
    assert step == 3 * (6 * by_hand + head)
    # the rule of thumb, 6 FLOPs a parameter a token, plus attention
    assert step / tokens == pytest.approx(6 * params
                                          + 3 * 6 * 4 * attended * d,
                                          rel=1e-3)


def test_decode_and_prefill_work_by_hand():
    cfg = _config("dense-2048x24.json")
    params = flops.transformer_params(cfg)
    assert 1.30e9 < params < 1.32e9
    kv = 2 * 24 * 2048 * 2          # keys and values, bf16, a token
    assert flops.kv_bytes_per_token(cfg) == kv == 196608
    # 16 slots of 1,280 tokens: the cache the cell holds
    assert 16 * 1280 * kv == pytest.approx(4.03e9, rel=0.01)
    # one sequence at context 100: a forward over 1 token reading 100
    assert flops.transformer_forward_flops(cfg, 1, 100) == \
        24 * (2 * 12 * 2048 * 2048 + 4 * 100 * 2048) + 2 * 2048 * 50304
    assert flops.prefill_flops(cfg, 512) == \
        24 * (2 * 512 * 12 * 2048 * 2048 + 4 * 512 * 256.5 * 2048) \
        + 2 * 2048 * 50304


# -- the trace reduction ----------------------------------------------
def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)]) \
        == [(0, 3), (5, 9)]
    assert trace_reduce.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce.total([(0, 3), (5, 9)]) == 7
    assert trace_reduce.gaps([(2, 3), (5, 6)], 0, 10) \
        == [(0, 2), (3, 5), (6, 10)]


def _synthetic():
    ms = 1e6
    ops = [["fusion.1", 10 * ms, 4 * ms], ["fusion.2", 12 * ms, 4 * ms],
           ["copy.3", 30 * ms, 10 * ms], ["fusion.1", 95 * ms, 20 * ms]]
    mods = [["jit_step(1)", 10 * ms, 6 * ms], ["jit_other(2)", 30 * ms,
                                               10 * ms],
            ["jit_step(1)", 95 * ms, 20 * ms]]
    host = [["bench.window", 0.0, 100 * ms], ["bench.step", 0.0, 9 * ms],
            ["bench.block", 9 * ms, 8 * ms],
            ["bench.next_batch", 17 * ms, 12 * ms],
            ["bench.block", 29 * ms, 71 * ms], ["other", 0.0, 5.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "main",
                                         "events": host}]}]}


def test_busy_idle_and_module_time_on_a_synthetic_trace():
    trace = _synthetic()
    busy = trace_reduce.busy_seconds(trace)
    # union: [10,16] + [30,40] + [95,100 (clipped)] = 21 ms of 100
    assert busy == {"busy_s": pytest.approx(0.021),
                    "window_s": pytest.approx(0.100)}
    step = trace_reduce.module_seconds(trace, r"jit_step")
    assert step == {"seconds": pytest.approx(0.011), "runs": 2}
    assert trace_reduce.module_seconds(trace, r"nothing") is None
    top = trace_reduce.top_ops(trace)
    assert top[0] == ["copy.3", pytest.approx(0.010)]
    assert top[1] == ["fusion.1", pytest.approx(0.009)]
    gaps = dict(trace_reduce.idle_gaps(trace))
    # [0,10] mostly under bench.step, [16,30] under next_batch,
    # [40,95] under the second bench.block
    assert gaps == {"bench.step": pytest.approx(0.010),
                    "bench.next_batch": pytest.approx(0.014),
                    "bench.block": pytest.approx(0.055)}


def test_a_trace_without_a_chip_gives_nothing():
    trace = _synthetic()
    trace["planes"] = trace["planes"][1:]
    assert trace_reduce.busy_seconds(trace) is None
    assert trace_reduce.module_seconds(trace, "jit") is None
    assert trace_reduce.top_ops(trace) == []


def test_recorded_chip_trace():
    """A trace recorded on the v5e (PR 25): four calls of one small
    jitted program under the harness's spans."""
    with open(os.path.join(HERE, "fixtures", "v5e_probe_trace.json")) as f:
        trace = json.load(f)
    assert [p["name"] for p in trace_reduce.device_planes(trace)] \
        == ["/device:TPU:0"]
    busy = trace_reduce.busy_seconds(trace)
    assert 0 < busy["busy_s"] < busy["window_s"]
    ran = trace_reduce.module_seconds(trace, r"probe_step")
    assert ran["runs"] == 4
    # the programs' time holds the operations' time
    assert busy["busy_s"] <= ran["seconds"] * 1.001
    assert trace_reduce.top_ops(trace)
    gaps = trace_reduce.idle_gaps(trace)
    assert gaps and sum(s for _, s in gaps) == pytest.approx(
        busy["window_s"] - busy["busy_s"], rel=1e-6)


# -- the load generator -------------------------------------------------
TRAFFIC = {"rate": 2.0,
           "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
           "output": {"median": 128, "sigma": 0.6, "min": 16, "max": 256}}


def test_schedule_is_the_same_work_for_every_seed():
    a = loadgen.schedule(TRAFFIC, 7, 40.0, 50304)
    b = loadgen.schedule(TRAFFIC, 7, 40.0, 50304)
    c = loadgen.schedule(TRAFFIC, 3000000019, 40.0, 50304)
    assert len(a) == 80
    shape = [(r.due_s, len(r.prompt), r.max_new) for r in a]
    assert shape == [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert shape == [(r.due_s, len(r.prompt), r.max_new) for r in c]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    # the sizes are met in a shuffled order, not sorted
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)
    lens = sorted(len(r.prompt) for r in a)
    assert lens[0] >= 32 and lens[-1] <= 1024
    assert 230 <= lens[len(lens) // 2] <= 285       # the median
    assert max(r.max_new for r in a) <= 256
    assert a[-1].due_s == pytest.approx(40.0, rel=0.05)
    assert all(0 < r.prompt.min() and r.prompt.max() < 50304 for r in a)


def test_summarize_times_from_due_and_counts_the_missing():
    plan = loadgen.schedule(TRAFFIC, 1, 10.0, 1000)[:4]
    for i, r in enumerate(plan):
        r.due_s, r.sent_s = float(i), float(i) + 0.01
    plan[0].token_s = [0.5, 0.6, 0.8]
    plan[1].token_s = [1.2, 1.3, 10.5]       # last token after the close
    plan[2].token_s = [2.1]
    plan[3].outcome = "shed:queue_full"      # never got a token
    got = loadgen.summarize(plan, 10.0, 70.0)
    assert got["attempted"] == 4 and got["failed"] == 1
    assert got["tokens_in_window"] == 6 and got["token_gaps"] == 3
    assert got["serve_tokens_per_s"] == pytest.approx(0.6)
    assert got["ttft_p50_ms"] == pytest.approx(200.0)
    assert got["ttft_p90_ms"] == pytest.approx(67000.0)   # 70 - 3
    assert got["tpot_p95_ms"] == pytest.approx(200.0)
    assert got["late_ms"] == pytest.approx([10.0] * 4)


def test_a_warm_up_is_the_same_schedule_begun_before_the_window():
    warm = dict(TRAFFIC, warm_seconds=10.0)
    a = loadgen.schedule(warm, 7, 40.0, 50304)
    assert len(a) == 100                     # 2/s over 10 + 40 s
    assert a[0].due_s < -9.0 and a[-1].due_s == pytest.approx(40.0,
                                                              rel=0.05)
    # Poisson: about 20 of the 100 fall into the 10 s before the window
    assert 10 <= sum(1 for r in a if r.due_s < 0.0) <= 30
    # the same work for every seed, the warm-up's too
    b = loadgen.schedule(warm, 11, 40.0, 50304)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] \
        == [(r.due_s, len(r.prompt), r.max_new) for r in b]


class _Quiet:
    def __call__(self, name):
        import contextlib
        return contextlib.nullcontext()


def test_offer_sends_the_warm_up_first_and_each_request_once():
    import time

    plan = loadgen.schedule(dict(TRAFFIC, rate=100.0, warm_seconds=0.1),
                            1, 0.2, 1000)
    sent = []
    opens = time.perf_counter() + 0.1
    loadgen.offer(plan, sent.append, 0.0, opens, _Quiet())
    assert time.perf_counter() >= opens
    assert sent and all(r.due_s < 0.0 for r in sent)
    warm = len(sent)
    assert warm == sum(1 for r in plan if r.due_s < 0.0)
    loadgen.offer(plan, sent.append, 0.2, time.perf_counter(), _Quiet())
    assert len(sent) == len({r.index for r in sent})
    assert all(0.0 <= r.due_s < 0.2 for r in sent[warm:])
    assert len(sent) == sum(1 for r in plan if r.due_s < 0.2)


@pytest.mark.parametrize("what", ["due_before", "straddles", "warm_failed"])
def test_summarize_with_a_warm_up(what):
    """A request due before the window is in no time to first token and
    not in ``attempted``; one that straddles the opening gives the
    window only its tokens and gaps inside; a warm-up request that
    failed is a failed operation."""
    plan = loadgen.schedule(TRAFFIC, 1, 10.0, 1000)[:3]
    for r, due in zip(plan, (-2.0, -0.5, 1.0)):
        r.due_s, r.sent_s = due, due + 0.01
    plan[0].token_s = [-1.9, -1.0, -0.2]      # all before the opening
    plan[1].token_s = [-0.3, -0.1, 0.2, 0.5, 0.6]
    plan[2].token_s = [1.4, 1.5]
    if what == "warm_failed":
        plan[0].outcome = "error:ExecutorFailure"
    got = loadgen.summarize(plan, 10.0, 70.0)
    if what == "due_before":
        assert got["attempted"] == 1 and got["failed"] == 0
        assert got["ttft_p50_ms"] == pytest.approx(400.0)
        assert got["ttft_p90_ms"] == pytest.approx(400.0)
        assert got["late_ms"] == pytest.approx([10.0])
    elif what == "straddles":
        # 3 of the straddler's 5 tokens, 2 of its 4 gaps (the gap from
        # -0.1 to 0.2 began before the opening), and the third
        # request's 2 tokens and 1 gap
        assert got["tokens_in_window"] == 5 and got["token_gaps"] == 3
        assert got["serve_tokens_per_s"] == pytest.approx(0.5)
        assert got["tpot_p95_ms"] == pytest.approx(300.0)
    else:
        assert got["attempted"] == 2 and got["failed"] == 1


@pytest.mark.parametrize("first, second, waiting, median, held", [
    # PR 34's sweep at 50 s on the chip (PERF.md section 6): 0.8, 0.9,
    # 0.95 (a backlog from the warm-up that stands), 1.0 and 1.1 a second
    (679.1, 77.3, 0, 77.1, True), (162.3, 292.2, 0, 91.6, True),
    (2801.2, 1448.9, 0, 2205.7, False), (68.5, 4803.5, 8, 82.5, False),
    (631.4, 8516.6, 15, 1924.8, False),
    # the first tokens keep up, and requests pile up all the same
    (100.0, 120.0, 4, 110.0, False)])
def test_the_sweeps_rule_for_a_sustained_rate(first, second, waiting,
                                              median, held):
    from perfbench import sweep

    assert sweep.sustained(first, second, waiting, median) is held


def test_percentile_is_a_value_of_the_sample():
    values = list(range(1, 101))
    assert loadgen.percentile(values, 0.90) == 90
    assert loadgen.percentile(values, 0.95) == 95
    assert loadgen.percentile([3.0], 0.9) == 3.0


# -- weights from the seed ----------------------------------------------
def test_weights_are_the_seeds_and_bfloat16_exact():
    specs = [("a", (4, 8), ("normal", 0.02)), ("g", (8,), ("const", 1.0)),
             ("b", (8, 4), ("normal", 0.5))]
    big = 3000000019
    one = weights.make_all(big, specs, "float32")
    two = weights.make_all(big, specs, "float32")
    other = weights.make_all(big + 1, specs, "float32")
    only_b = weights.make_all(big, specs, "float32", only={"b"})
    assert set(only_b) == {"b"}
    for k in one:
        assert (np.asarray(one[k]) == np.asarray(two[k])).all()
    assert (np.asarray(only_b["b"]) == np.asarray(one["b"])).all()
    assert (np.asarray(one["a"]) != np.asarray(other["a"])).any()
    assert (np.asarray(one["g"]) == 1.0).all()
    a = np.asarray(one["a"])
    assert (a == np.asarray(one["a"].astype("bfloat16")
                            .astype("float32"))).all()
    moved = {"a": one["a"] + 0.5, "b": one["b"]}
    change = weights.change_norms(big, specs, moved)
    assert float(change["a"]) == pytest.approx(0.5 * math.sqrt(32))
    assert float(change["b"]) == 0.0
