"""Generation-tier unit tests (the KV-cache decode serving PR): the
shared bucket-ladder helper pins its plans, a GenerationRuntime's plan
geometry is fixed at construction, and the decode-bucket auditor flags
its seeded fixture.  Nothing here compiles — the real-model engine
e2e (greedy equality, recompile discipline, cancel storm, streaming
HTTP) lives in tests/test_zz_generate_e2e.py, named to sort after the
transformer suite so its XLA compile cost lands at the tail of a
time-boxed tier-1 run."""
import numpy as np
import pytest

from mxnet_tpu import serving


# ---------------------------------------------------------------------
# bucket ladders: the shared planning helper (no compiles)
# ---------------------------------------------------------------------
def test_ladder_plans_pinned():
    # bit-for-bit the historical plan_batch_buckets ladder
    assert serving.ladder(32) == (1, 2, 4, 8, 16, 32)
    assert serving.ladder(32) == serving.plan_batch_buckets(32)
    # non-power cap is appended, never rounded away
    assert serving.ladder(6) == (1, 2, 4, 6)
    assert serving.ladder(1) == (1,)
    # the generation axes floor at one cache block
    assert serving.ladder(64, min_size=16) == (16, 32, 64)
    # explicit sizes: sorted, deduped, capped, cap appended
    assert serving.ladder(8, sizes=[4, 2, 4, 99]) == (2, 4, 8)


def test_bucket_for_exhaustive_disjoint_cover():
    plan = serving.ladder(32)
    for n in range(1, 33):
        b = serving.bucket_for(plan, n)
        assert b >= n
        # smallest holding bucket: every size maps to exactly one
        smaller = [x for x in plan if x < b]
        if smaller:
            assert max(smaller) < n
        # doubling ladder bounds padding waste below 2x
        assert b < 2 * n or b == 1
    with pytest.raises(ValueError):
        serving.bucket_for(plan, 33)


def test_ladder_2d_cover_and_mapping():
    plan = serving.ladder_2d(4, 64, min_b=16)
    assert plan == tuple((a, b) for a in (1, 2, 4)
                         for b in (16, 32, 64))
    for na in range(1, 5):
        for nb in range(1, 65):
            ba, bb = serving.bucket_for_2d(plan, na, nb)
            assert (ba, bb) in plan and ba >= na and bb >= nb
    with pytest.raises(ValueError):
        serving.bucket_for_2d(plan, 5, 16)


def test_generation_runtime_plans_pinned():
    # plan geometry is fixed at construction (no compile needed)
    grt = serving.demo_generation_runtime(
        "gen_plan", n_layers=1, slots=4, block_tokens=16,
        max_prompt=20, max_context=64, max_new=8, prefill_batch=2)
    assert grt.max_prompt == 32          # rounded up to a block multiple
    assert grt.prompt_plan == (16, 32)
    assert grt.cache_plan == (16, 32, 64)
    assert grt.batch_plan == (1, 2, 4)
    assert grt.prefill_plan == tuple(
        (a, b) for a in (1, 2) for b in (16, 32))
    assert grt.decode_plan == tuple(
        (a, b) for a in (1, 2, 4) for b in (16, 32, 64))
    # auto pool: every slot can reach max_context, +1 garbage block
    assert grt.kv.num_blocks == 4 * (64 // 16) + 1


# ---------------------------------------------------------------------
# decode-bucket auditor: seeded fixture flagged, fixed twin clean
# ---------------------------------------------------------------------
def test_decode_bucket_auditor_fixture():
    from mxnet_tpu.analysis import auditor, fixtures

    plan, observed, counts = fixtures.decode_bucket_violation()
    hits = auditor.check_decode_buckets(plan, observed, "fx",
                                        compile_counts=counts)
    kinds = {f.details.get("fingerprint_key", "").split(":")[0]
             for f in hits}
    assert {"shape", "total"} <= kinds, [f.to_dict() for f in hits]
    cplan, cobs, ccounts = fixtures.decode_bucket_clean()
    assert not auditor.check_decode_buckets(cplan, cobs, "fx_clean",
                                            compile_counts=ccounts)


# ---------------------------------------------------------------------
# the host-stub engine drive: real engine/allocator/plans, numpy cells
# ---------------------------------------------------------------------
def test_stub_engine_greedy_matches_reference():
    # the same drive the serving self-test groups 10-13 build on: the
    # arithmetic token rule reads back THROUGH the block tables, so a
    # broken allocator or table diverges from the reference
    rt = serving.StubGenerationRuntime(
        "gen_stub_t", slots=2, max_prompt=16, max_context=32,
        block_tokens=16, max_new=8, prefill_batch=2)
    rt.compile(warmup=True)
    prompts = [[1, 2, 3], list(range(1, 13)), [7] * 5]
    reqs = [serving.GenRequest("gen_stub_t", p, 6) for p in prompts]
    for r in reqs:
        rt.engine.enqueue(r)
    while not rt.engine.idle():
        rt.engine.step()
    for p, r in zip(prompts, reqs):
        assert r.wait(0.1)["tokens"] == serving.stub_greedy_reference(
            p, 6)
    assert rt.kv.stats()["blocks_live"] == 0


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_stub_steps_hand_back_int32_ids(kind):
    # the stub's numpy cells keep the compiled steps' contract: ``bb``
    # int32 ids, the token rule's, which the engine reads as they are;
    # two riders and two empty slots
    rt = serving.StubGenerationRuntime(
        "gen_stub_ids", slots=4, max_prompt=16, max_context=32,
        block_tokens=16, max_new=4, prefill_batch=4)
    rt.compile(warmup=False)
    prompts = [[1, 2, 3], [4, 5]]
    tokens = np.zeros((4, 16), np.int32)
    tables = np.zeros((4, 2), np.int32)
    for i, p in enumerate(prompts):
        rt.kv.alloc(i, len(p) + 1)
        tokens[i, :len(p)] = p
        tables[i, 0] = rt.kv.block_table(i, 1)[0]
    ids, pages = rt._prefill[(4, 16)](
        rt._params, tokens, np.asarray([3, 2, 1, 1], np.int32),
        rt.kv.pages, tables[:, :1])
    if kind == "decode":
        ids, _ = rt._decode[(4, 32)](
            rt._params, np.asarray(list(ids[:2]) + [0, 0], np.int32),
            np.asarray([3, 2, 0, 0], np.int32), pages, tables)
    assert ids.dtype == np.int32 and ids.shape == (4,)
    n = ("prefill", "decode").index(kind)
    assert ids[:2].tolist() == [serving.stub_greedy_reference(p, 2)[n]
                                for p in prompts]


def test_stub_runtime_donates_nothing():
    # the stub's cells are numpy functions, no jit: compile() counts
    # none of its pools as donated, and no failure can lose them
    from mxnet_tpu import profiler

    rt = serving.StubGenerationRuntime(
        "gen_stub_d", slots=1, max_prompt=16, max_context=16,
        block_tokens=16, max_new=2, prefill_batch=1)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=True)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    assert stamped["kv.pools_donated"]["max"] == 0
    assert stamped["kv.pools"]["max"] == 2
    assert not rt.kv.pools_lost()


def test_stub_runtime_stamps_no_decode_attention_site():
    # the stub's cells are numpy functions: compile() stamps the latent
    # decode's attention sites once, and both are 0
    from mxnet_tpu import profiler

    rt = serving.StubGenerationRuntime(
        "gen_stub_s", slots=1, max_prompt=16, max_context=16,
        block_tokens=16, max_new=2, prefill_batch=1)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=True)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    for how in ("kernel", "gather"):
        site = stamped["attn.decode_%s_sites" % how]
        assert (site["max"], site["count"]) == (0, 1)


# ---------------------------------------------------------------------
# the engine's phase records, driven through ModelServer
# ---------------------------------------------------------------------
# what the engine and its worker add to the ring beside the compiled
# call's own span (``mx.tick`` / ``mx.prefill``) and the launch under it
PHASE_RECORDS = {"mx.serve.loop", "mx.engine.prepare", "mx.engine.stream",
                 "mx.tick.readback", "mx.prefill.readback"}


class _Ids:
    """A compiled call's ids that count their reads to the host."""

    reads = 0

    def __init__(self, value):
        self.value = value

    def __array__(self, *a, **kw):
        type(self).reads += 1
        return self.value


def _counted(cells, calls):
    def wrap(step):
        def call(*args):
            calls.append(1)
            ids, pages = step(*args)
            return _Ids(ids), pages
        return call
    for key, step in list(cells.items()):
        cells[key] = wrap(step)


def _worker_records(t0, t1):
    from mxnet_tpu import profiler

    spans = profiler.spans_between(t0, t1)
    (thread,) = {s.thread for s in spans if s.name == "mx.serve.loop"}
    return [s for s in spans if s.thread == thread]


def _iterations(records):
    """``(loop record, [records inside it])`` for every worked iteration."""
    loops = [s for s in records if s.name == "mx.serve.loop"]
    return [(loop, [s for s in records if s is not loop
                    and loop.t0 <= s.t0 and s.t1 <= loop.t1])
            for loop in loops]


@pytest.fixture
def served_stub(monkeypatch):
    import jax

    import time as _time

    rt = serving.StubGenerationRuntime(
        "gen_phases_t", slots=8, max_prompt=16, max_context=64,
        block_tokens=16, max_new=32, prefill_batch=1)
    srv = serving.ModelServer(queue_max=64)
    srv.add_generator(rt)
    calls = []
    _counted(rt._decode, calls)
    _counted(rt._prefill, calls)
    _Ids.reads = 0
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda *a, **k: syncs.append(a))
    monkeypatch.setattr(jax, "device_get", lambda *a, **k: syncs.append(a))
    t0 = _time.perf_counter()
    yield srv, calls, syncs, t0
    srv.drain(timeout_s=10)


def _generate(srv, prompts, max_new):
    reqs = [srv.submit_generation("gen_phases_t", p, max_new=max_new)
            for p in prompts]
    for p, r in zip(prompts, reqs):
        assert r.wait(10)["tokens"] == serving.stub_greedy_reference(
            p, max_new)


def test_engine_records_each_worked_iteration_in_o1_records(served_stub):
    import time

    srv, calls, syncs, t0 = served_stub
    _generate(srv, [[1, 2, 3]], 12)                     # one rider
    _generate(srv, [[i + 1, 2, 3] for i in range(8)], 20)  # eight
    srv.drain(timeout_s=10)
    records = _worker_records(t0, time.perf_counter())
    assert {"mx.engine.prepare", "mx.tick.readback", "mx.engine.stream",
            "mx.prefill.readback"} <= {s.name for s in records}
    added = {}                # riders -> new records a decode-only pass
    for loop, inside in _iterations(records):
        names = [s.name for s in inside]
        new = 1 + sum(n in PHASE_RECORDS for n in names)
        if "mx.prefill" in names:
            assert new <= 8
            continue
        (tick,) = [s for s in inside if s.name == "mx.tick"]
        assert new <= 5
        added.setdefault(tick.args["live"], set()).add(new)
        # the nesting: the phases one level under the loop, the
        # readback inside the call's span; prepare ends where the call
        # begins and the stream begins after it
        (prep,) = [s for s in inside if s.name == "mx.engine.prepare"]
        (back,) = [s for s in inside if s.name == "mx.tick.readback"]
        (stream,) = [s for s in inside if s.name == "mx.engine.stream"]
        assert prep.depth == tick.depth == stream.depth == loop.depth + 1
        assert back.depth == loop.depth + 2
        assert prep.t1 == tick.t0 and tick.t0 <= back.t0 <= back.t1 \
            <= tick.t1 <= stream.t0
    # as many records with one rider as with eight
    assert added[1] == added[8] == {4}
    # one read of the ids a compiled call; no other device sync
    assert calls and _Ids.reads == len(calls)
    assert not syncs


def test_idle_iterations_fold_into_one_record(served_stub):
    import time

    srv, _, _, t0 = served_stub
    _generate(srv, [[5, 6]], 4)
    time.sleep(0.05)                 # some fifty idle passes
    between = time.perf_counter()
    _generate(srv, [[7, 8]], 4)
    srv.drain(timeout_s=10)
    records = [s for s in _worker_records(t0, time.perf_counter())
               if s.name in ("mx.serve.loop", "mx.serve.idle")]
    records.sort(key=lambda s: s.t0)
    # worked and idle runs alternate: never two idle records in a row
    assert all("mx.serve.idle" != a.name or a.name != b.name
               for a, b in zip(records, records[1:]))
    (gap,) = [s for s in records if s.name == "mx.serve.idle"
              and s.t0 < between < s.t1]
    assert gap.t1 - gap.t0 >= 0.04
    # the drain closes the last idle run
    assert records[-1].name == "mx.serve.idle"


def test_held_metrics_follow_a_cleared_registry(monkeypatch):
    # the allocator's gauges are looked up once and held; a registry
    # cleared (or replaced) since gets them again on the next feed
    from mxnet_tpu import diagnostics as diag

    monkeypatch.setattr(diag, "metrics", diag.MetricsRegistry())
    rt = serving.StubGenerationRuntime(
        "gen_held_t", slots=1, max_prompt=16, max_context=16,
        block_tokens=16, max_new=2, prefill_batch=1)
    for clear in (False, True):
        if clear:
            diag.metrics.clear()
        rt.kv.feed_metrics()
        prom = diag.metrics.to_prom()
        assert 'mxnet_serve_kv_blocks_free{model="gen_held_t"} %d' % (
            rt.kv.num_blocks - 1) in prom
        assert 'mxnet_serve_kv_pool_rebuilds_total{model="gen_held_t"} 0' \
            in prom
