"""Generation-tier unit tests (the KV-cache decode serving PR): the
shared bucket-ladder helper pins its plans, a GenerationRuntime's plan
geometry is fixed at construction, and the decode-bucket auditor flags
its seeded fixture.  Nothing here compiles — the real-model engine
e2e (greedy equality, recompile discipline, cancel storm, streaming
HTTP) lives in tests/test_zz_generate_e2e.py, named to sort after the
transformer suite so its XLA compile cost lands at the tail of a
time-boxed tier-1 run."""
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
