"""Generation-engine e2e on the REAL transformer (XLA-compiled plan
cells): the paged scatter/gather round trip is bitwise invisible to
attention, greedy continuous-batched paged decode matches the
dense-cache whole-prompt reference token for token (including a
cache-bucket promotion mid-generation), finished slots refill without
draining co-riders, every plan cell stays at its single warmup compile
under mixed traffic (and the decode auditor agrees), a chaos cancel
storm leaks zero blocks, the donated pools are written in place and
made again when a failed step has consumed them, and token streaming
works end-to-end over chunked HTTP.

The ``zz`` prefix is deliberate: this module sorts after
test_transformer.py so its XLA compile cost lands at the tail of a
time-boxed tier-1 run — the cheap no-compile generation units live in
tests/test_generate.py."""
import json
import socket
import struct
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import chaos
from mxnet_tpu import diagnostics as diag
from mxnet_tpu import serving
from mxnet_tpu.serving import reqtrace
from mxnet_tpu.transformer import model as tm


# ---------------------------------------------------------------------
# paged scatter -> block-table gather == the dense cache, BITWISE
# ---------------------------------------------------------------------
def test_scatter_gather_matches_dense_attention_bitwise():
    import jax.numpy as jnp

    bt, H, Dh = 16, 2, 8
    # ragged lengths straddling block/bucket boundaries
    lens = [3, 16, 17, 33]
    B, W = len(lens), 3                  # 3 blocks cover max len 33
    T = W * bt
    rng = np.random.RandomState(0)
    dense_k = rng.randn(B, T, H, Dh).astype(np.float32)
    dense_v = rng.randn(B, T, H, Dh).astype(np.float32)
    tables = np.zeros((B, W), dtype=np.int32)
    nxt = 1                              # block 0 is the garbage block
    for i, ln in enumerate(lens):
        nb = -(-ln // bt)
        tables[i, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
    pool_shape = (nxt, bt, H, Dh)
    pos = np.broadcast_to(np.arange(T), (B, T))
    valid = pos < np.asarray(lens)[:, None]
    k_pool = tm._scatter_tokens(jnp.zeros(pool_shape, jnp.float32),
                                jnp.asarray(dense_k),
                                jnp.asarray(tables), jnp.asarray(pos),
                                bt, valid=jnp.asarray(valid))
    v_pool = tm._scatter_tokens(jnp.zeros(pool_shape, jnp.float32),
                                jnp.asarray(dense_v),
                                jnp.asarray(tables), jnp.asarray(pos),
                                bt, valid=jnp.asarray(valid))
    gk, gv = tm.gather_kv({"k0": k_pool, "v0": v_pool},
                          jnp.asarray(tables), 0)
    gk, gv = np.asarray(gk), np.asarray(gv)
    # the gathered valid region is the dense cache, bit for bit
    for i, ln in enumerate(lens):
        assert np.array_equal(gk[i, :ln], dense_k[i, :ln])
        assert np.array_equal(gv[i, :ln], dense_v[i, :ln])
    # and attention under the length mask cannot tell them apart:
    # identical inputs on the valid rows + masked scores on the rest
    q = jnp.asarray(rng.randn(B, 1, H, Dh).astype(np.float32))
    mask = jnp.asarray(pos[:, None, :] < np.asarray(lens)[:, None,
                                                          None])
    out_paged = tm._masked_attn(q, jnp.asarray(gk), jnp.asarray(gv),
                                mask)
    out_dense = tm._masked_attn(q, jnp.asarray(dense_k),
                                jnp.asarray(dense_v), mask)
    assert np.array_equal(np.asarray(out_paged), np.asarray(out_dense))


# ---------------------------------------------------------------------
# the engine: greedy equality, continuous refill, recompile discipline
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def grt():
    rt = serving.demo_generation_runtime(
        "gen_t", n_layers=1, slots=2, block_tokens=16, max_prompt=16,
        max_context=64, max_new=32, prefill_batch=2)
    rt.compile(warmup=True)
    return rt


def _dense_greedy(rt, prompt, n_new):
    import jax.numpy as jnp

    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        arr = np.asarray(toks, dtype=np.int32)  # mxlint: disable=MXL004
        logits = tm.apply(rt._params, jnp.asarray(arr[None]), rt.cfg,
                          attn_fn=tm.dense_causal_attn)
        last = np.asarray(logits)  # mxlint: disable=MXL004
        nxt = int(last[0, -1].argmax())
        out.append(nxt)
        toks.append(nxt)
    return out


def test_greedy_matches_dense_reference_across_promotion(grt):
    # prompt 12 + 24 new tokens ends at 36: the sequence crosses the
    # 16- and 32-token cache buckets mid-generation (two promotions);
    # 3 requests on 2 slots also forces a waiting-line admission
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, grt.cfg.vocab_size, size=n).tolist()
               for n in (3, 12, 16)]
    reqs = [serving.GenRequest("gen_t", p, 24) for p in prompts]
    for r in reqs:
        grt.engine.enqueue(r)
    while not grt.engine.idle():
        grt.engine.step()
    for p, r in zip(prompts, reqs):
        got = r.wait(0.1)["tokens"]
        assert got == _dense_greedy(grt, p, 24), \
            "paged/continuous greedy diverged for prompt len %d" % len(p)
    assert grt.kv.stats()["blocks_live"] == 0


def test_continuous_batching_refills_slots(grt):
    # 5 sequences on 2 slots, 8 tokens each: serial would cost 40
    # decode ticks — continuous refill lands well under that
    t0 = grt.engine.ticks
    reqs = [serving.GenRequest("gen_t", [i + 1, i + 2], 8)
            for i in range(5)]
    for r in reqs:
        grt.engine.enqueue(r)
    while not grt.engine.idle():
        grt.engine.step()
    assert all(len(r.wait(0.1)["tokens"]) == 8 for r in reqs)
    assert grt.engine.ticks - t0 < 32
    assert grt.kv.stats()["blocks_live"] == 0


def test_zero_steady_state_recompiles_and_audit_clean(grt):
    # drive fresh mixed-shape traffic, then prove every plan cell is
    # still at its single warmup compile and the auditor agrees
    for p, n in (([1, 2, 3], 6), (list(range(1, 14)), 20)):
        r = serving.GenRequest("gen_t", p, n)
        grt.engine.enqueue(r)
        while not grt.engine.idle():
            grt.engine.step()
        r.wait(0.1)
    counts = {k: v["count"] for k, v in diag.recompile_stats().items()
              if ":gen_t:" in k}
    assert len(counts) == len(grt.prefill_plan) + len(grt.decode_plan)
    assert set(counts.values()) == {1}, counts
    from mxnet_tpu import analysis

    rep = analysis.audit_decode_buckets()
    site = "generate_decode:gen_t"
    assert site in rep.sites
    assert not [f for f in rep.findings
                if f.site == site], rep.summary()
    assert rep.sites[site]["compiles"] == len(grt.decode_plan)


def test_cancel_storm_zero_leaked_blocks(grt, monkeypatch):
    # chaos cancel_request: 4 mid-stream disconnects across the run;
    # cancelled sequences reclaim slot+blocks next tick, co-riders
    # finish their full 16 tokens
    monkeypatch.setenv("MXNET_CHAOS",
                       "cancel_request:model=gen_t,nth=3,count=4")
    chaos.reset()
    reqtrace.reset(capacity=32, topk=4)
    try:
        reqs = [serving.GenRequest("gen_t", [i + 1, i + 7, i + 3], 16)
                for i in range(6)]
        for r in reqs:
            grt.engine.enqueue(r)
        while not grt.engine.idle():
            grt.engine.step()
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
        snap = reqtrace.snapshot()
        reqtrace.reset()
    cancelled = ok = 0
    for r in reqs:
        try:
            res = r.wait(0.1)
            assert len(res["tokens"]) == 16  # co-riders untouched
            ok += 1
        except serving.Cancelled:
            cancelled += 1
    assert cancelled == 4 and ok == 2
    assert grt.kv.stats()["blocks_live"] == 0
    assert grt.kv.stats()["blocks_free"] == grt.kv.num_blocks - 1
    # ...and the storm leaves the request-trace ring CONSISTENT: every
    # record reached a terminal span (no orphan open records), with the
    # same 4-cancelled/2-ok split the futures report
    assert not snap["open"], [r["id"] for r in snap["open"]]
    outcomes = [r["outcome"] for r in snap["recent"]]
    assert outcomes.count("cancelled") == 4
    assert outcomes.count("ok") == 2


def test_reqtrace_deadline_expiry_dies_waiting(grt, monkeypatch,
                                               tmp_path):
    # two blockers occupy both slots; the doomed request's 5 ms
    # deadline expires in the waiting line.  Its terminal reqtrace
    # span must say expired-while-WAITING (queue residency only, no
    # execute phase), and the blown deadline must auto-dump the
    # autopsy file
    monkeypatch.setenv("MXNET_DUMP_DIR", str(tmp_path))
    reqtrace.reset(capacity=32, topk=4)
    try:
        blockers = [serving.GenRequest("gen_t", [1, 2, 3], 12)
                    for _ in range(2)]
        for r in blockers:
            grt.engine.enqueue(r)
        grt.engine.step()  # both slots now occupied
        doomed = serving.GenRequest("gen_t", [4, 5], 12,
                                    deadline_s=0.001)
        grt.engine.enqueue(doomed)
        time.sleep(0.01)  # the deadline lapses in the waiting line
        while not grt.engine.idle():
            grt.engine.step()
        with pytest.raises(serving.DeadlineExceeded):
            doomed.wait(0.1)
        for r in blockers:
            assert len(r.wait(0.1)["tokens"]) == 12
        snap = reqtrace.snapshot()
        assert not snap["open"]
        rec = next(r for r in snap["recent"] if r["id"] == doomed.id)
        assert rec["outcome"] == "expired"
        assert "queue" in rec["phases"]
        assert not any(k in rec["phases"]
                       for k in ("prefill", "decode", "execute"))
        assert reqtrace.recorder.model_summary()["gen_t"][
            "died_waiting"] >= 1
        dumps = sorted(tmp_path.glob("reqtrace_rank*.json"))
        assert dumps, "a blown deadline must auto-dump the autopsy"
        payload = json.loads(dumps[0].read_text())
        assert payload["header"]["reason"] == "deadline"
        assert payload["header"]["format"] == reqtrace.REQTRACE_FORMAT
    finally:
        reqtrace.reset()
    assert grt.kv.stats()["blocks_live"] == 0


# ---------------------------------------------------------------------
# the K and V pools are donated: written in place, dead once handed
# in, and made again when a failed step has consumed them
# ---------------------------------------------------------------------
def _live(pages):
    return not any(a.is_deleted() for a in pages.values())


def _dead(pages):
    return all(a.is_deleted() for a in pages.values())


@pytest.fixture(scope="module")
def grt2():
    """A two-layer runtime compiled under the profiler: (runtime, the
    counters its compile() stamped, the pools it was built with)."""
    from mxnet_tpu import profiler

    rt = serving.demo_generation_runtime(
        "gen_d", n_layers=2, slots=2, block_tokens=16, max_prompt=16,
        max_context=32, max_new=8, prefill_batch=1)
    built_with = rt.kv.pages
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=True)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    return rt, stamped, built_with


def test_compile_counts_the_pools_donated(grt2):
    rt, stamped, built_with = grt2
    pools = 2 * rt.cfg.n_layers
    assert len(rt.kv.pages) == pools
    assert stamped["kv.pools_donated"]["max"] == pools
    assert stamped["kv.pools"]["max"] == pools
    assert stamped["kv.pools"]["count"] == 1        # once a compile()
    # warm-up handed each cell what the cell before gave back
    assert _dead(built_with) and _live(rt.kv.pages)


def test_compile_counts_no_latent_decode_site_of_the_dense_block(grt2):
    # the dense block's decode attends through its own gather and mask
    _, stamped, _ = grt2
    for how in ("kernel", "gather"):
        site = stamped["attn.decode_%s_sites" % how]
        assert (site["max"], site["count"]) == (0, 1)


def test_compile_counts_the_latent_decode_sites_once():
    """``attn.decode_kernel_sites`` / ``attn.decode_gather_sites``: the
    latent decode step's attention, one site for each block kind traced
    (a dense layer and an expert layer), stamped once a ``compile()``.
    On the CPU, under the tests' x64, every site gathers; on the chip
    every one is the paged kernel (tests/test_chip_compile.py)."""
    import jax

    from mxnet_tpu import profiler
    from mxnet_tpu.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, n_layers=3, d_model=32, n_heads=2, d_ff=64,
        attn_kind="latent", kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, ffn_act="swiglu",
        tied_head=False, layer_kinds=("dense_ffn", "experts", "experts"),
        n_experts=4, experts_per_token=2, n_shared_experts=1, expert_ff=16,
        held_experts=(0, 1))
    rt = serving.GenerationRuntime(
        "gen_latent_sites", init_params(jax.random.PRNGKey(0), cfg), cfg,
        slots=2, block_tokens=8, max_prompt=8, max_context=16, max_new=4,
        prefill_batch=1)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=False)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    kernel = stamped["attn.decode_kernel_sites"]
    gather = stamped["attn.decode_gather_sites"]
    assert (kernel["max"], kernel["count"]) == (0, 1)
    assert (gather["max"], gather["count"]) == (2, 1)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_compiled_step_writes_every_pool_in_place(grt2, kind):
    """The compiled step aliases each of the 2 x n_layers pools to its
    own output and holds no copy of a pool's shape."""
    import re

    import jax

    rt = grt2[0]
    cells = rt._prefill if kind == "prefill" else rt._decode
    (bb, tb), step = max(cells.items())
    bt = rt.block_tokens

    def ints(*shape):
        return np.zeros(shape, dtype=np.int32)

    tokens = ints(bb, tb) if kind == "prefill" else ints(bb)
    text = step.lower(rt._params, tokens, ints(bb), rt.kv.pages,
                      ints(bb, tb // bt)).compile().as_text()
    head = next(ln for ln in text.splitlines()
                if ln.startswith("HloModule"))
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", alias.group(1))
    # flat arguments: the parameters' leaves, tokens, lengths or
    # positions, then the pools; flat results: the ids, then the pools
    first = len(jax.tree_util.tree_leaves(rt._params)) + 2
    pools = 2 * rt.cfg.n_layers
    assert sorted((int(o), int(i)) for o, i in pairs) == [
        (1 + j, first + j) for j in range(pools)]
    a_pool = next(iter(rt.kv.pages.values()))
    shape = "f32[%s]" % ",".join(str(n) for n in a_pool.shape)
    assert shape in head
    assert not [ln for ln in text.splitlines()
                if shape in ln.split("=")[-1].split("(")[0]
                and re.search(r"\bcopy\(", ln)]
    assert _live(rt.kv.pages)       # lowering consumes nothing


def test_every_step_consumes_the_pools_it_was_handed(grt):
    # max_new 1 retires at its prefill: a step that is a prefill alone
    one = serving.GenRequest("gen_t", [3, 1, 4], 1)
    grt.engine.enqueue(one)
    handed = grt.kv.pages
    grt.engine.step()
    assert len(one.wait(0.1)["tokens"]) == 1
    assert _dead(handed) and _live(grt.kv.pages)
    # then a prefill with its decode tick, and decode ticks alone
    req = serving.GenRequest("gen_t", [3, 1, 4], 5)
    grt.engine.enqueue(req)
    while not grt.engine.idle():
        handed = grt.kv.pages
        grt.engine.step()
        assert _dead(handed) and _live(grt.kv.pages)
    assert req.wait(0.1)["tokens"] == _dense_greedy(grt, [3, 1, 4], 5)


def _consumes_then_raises(step):
    """As a device fault inside the step reads to the engine: the
    donated pools are gone and the call raises."""
    def call(params, tokens, positions, pages, tables):
        for a in pages.values():
            a.delete()
        raise RuntimeError("planted: the step died holding the pools")
    return call


def _result_unreadable(step):
    """As a run that fails on the device after its dispatch: the call
    returns, and reading the ids raises."""
    class Unreadable:
        def __array__(self, *a, **kw):
            raise RuntimeError("planted: the run failed on the device")

    def call(params, tokens, positions, pages, tables):
        _, pages = step(params, tokens, positions, pages, tables)
        return Unreadable(), pages
    return call


def _planted(cells, how):
    """Every cell of one of the runtime's step tables wrapped, as
    perfbench's serve_lm._planted wraps them; returns the sound ones."""
    sound = dict(cells)
    for key, step in sound.items():
        cells[key] = how(step)
    return sound


def _rebuilds_counted(rt):
    return diag.metrics.counter("mxnet_serve_kv_pool_rebuilds_total",
                                labels={"model": rt.name}).value


def _compiles(rt):
    return {k: v["count"] for k, v in diag.recompile_stats().items()
            if ":%s:" % rt.name in k}


@pytest.mark.parametrize("how", [_consumes_then_raises,
                                 _result_unreadable])
def test_decode_that_lost_the_pools_leaves_a_cache_that_works(grt, how):
    before = grt.kv.pool_rebuilds
    counted = _rebuilds_counted(grt)
    reqs = [serving.GenRequest("gen_t", p, 8)
            for p in ([5, 6, 7], [9, 8])]
    for r in reqs:
        grt.engine.enqueue(r)
    sound = _planted(grt._decode, how)
    try:
        rep = grt.engine.step()     # prefill is sound; the tick is not
    finally:
        grt._decode.update(sound)
    assert isinstance(rep["exec_error"], serving.ExecutorFailure)
    for r in reqs:
        with pytest.raises(serving.ExecutorFailure):
            r.wait(0.1)
    assert grt.engine.idle()
    assert grt.kv.stats()["blocks_live"] == 0
    assert _live(grt.kv.pages)
    assert grt.kv.pool_rebuilds == before + 1
    assert _rebuilds_counted(grt) == counted + 1
    # ...and the server serves on, correctly, in the cells it compiled
    nxt = serving.GenRequest("gen_t", [2, 7, 1, 8], 20)
    grt.engine.enqueue(nxt)
    while not grt.engine.idle():
        grt.engine.step()
    assert nxt.wait(0.1)["tokens"] == _dense_greedy(grt, [2, 7, 1, 8], 20)
    assert set(_compiles(grt).values()) == {1}, _compiles(grt)


@pytest.mark.parametrize("pools_lost", [True, False])
def test_prefill_failure_with_riders_active(grt, monkeypatch, pools_lost):
    """A prefill that consumed the pools takes the riders' history
    with it: they fail too.  One that raises before it is dispatched
    (chaos ``fail_execute``) fails its own group alone, as ever."""
    before = grt.kv.pool_rebuilds
    rider = serving.GenRequest("gen_t", [4, 4, 2], 12)
    grt.engine.enqueue(rider)
    grt.engine.step()
    assert len(rider.tokens) == 2 and not rider.done()
    late = serving.GenRequest("gen_t", [6, 1], 4)
    grt.engine.enqueue(late)
    if pools_lost:
        sound = _planted(grt._prefill, _consumes_then_raises)
    else:
        sound = dict(grt._prefill)
        monkeypatch.setenv("MXNET_CHAOS",
                           "fail_execute:model=gen_t,count=1")
        chaos.reset()
    try:
        rep = grt.engine.step()
    finally:
        grt._prefill.update(sound)
        monkeypatch.delenv("MXNET_CHAOS", raising=False)
        chaos.reset()
    assert isinstance(rep["exec_error"], serving.ExecutorFailure)
    with pytest.raises(serving.ExecutorFailure):
        late.wait(0.1)
    assert _live(grt.kv.pages)
    if pools_lost:
        with pytest.raises(serving.ExecutorFailure):
            rider.wait(0.1)
        assert grt.engine.idle()
        assert grt.kv.pool_rebuilds == before + 1
    else:
        assert not rider.done()
        while not grt.engine.idle():
            grt.engine.step()
        assert rider.wait(0.1)["tokens"] == _dense_greedy(
            grt, [4, 4, 2], 12)
        assert grt.kv.pool_rebuilds == before
    assert grt.kv.stats()["blocks_live"] == 0


# ---------------------------------------------------------------------
# streaming HTTP e2e: chunked :generate, per-token lines, cancel=499
# ---------------------------------------------------------------------
def test_http_generate_streaming_e2e(monkeypatch):
    rt = serving.demo_generation_runtime(
        "gen_http", n_layers=1, slots=1, block_tokens=16,
        max_prompt=16, max_context=32, max_new=8, prefill_batch=1)
    srv = serving.ModelServer(queue_max=8, default_deadline_ms=30000)
    srv.add_generator(rt)
    fe = serving.HttpFrontend(srv, port=0)
    host, port = fe.start()
    base = "http://%s:%d" % (host, port)
    try:
        # blocking path first: the reference token list
        req = urllib.request.Request(
            base + "/v1/models/gen_http:generate",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new": 6}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=30)
        blocking = json.loads(resp.read())
        assert resp.status == 200 and len(blocking["tokens"]) == 6
        # streaming path: urllib transparently de-chunks; the body is
        # one JSON line per token + the done record
        req = urllib.request.Request(
            base + "/v1/models/gen_http:generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_new": 6,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=30)
        assert resp.status == 200
        assert resp.headers.get("Transfer-Encoding") == "chunked"
        lines = [json.loads(ln) for ln in
                 resp.read().decode().splitlines() if ln]
        assert lines[-1] == {"done": True, "tokens": 6,
                             "prompt_len": 3}
        assert [ln["token"] for ln in lines[:-1]] == blocking["tokens"]
        assert [ln["index"] for ln in lines[:-1]] == list(range(6))
        # oversized prompt sheds at submit with too_large
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/gen_http:generate",
                data=json.dumps({"prompt": list(range(99))}).encode()))
        assert ei.value.code == 413
        assert json.loads(ei.value.read())["reason"] == "too_large"
        # streaming client-disconnect (the 499 convention): kill the
        # socket mid-stream under an injected decode stall (so the
        # engine is still generating when the RST lands); the terminal
        # reqtrace span must say cancelled — never ok — with the
        # disconnect event recorded and the stall spans tagged injected
        monkeypatch.setenv(
            "MXNET_CHAOS",
            "stall_decode_tick:model=gen_http,ms=30,count=999")
        chaos.reset()
        reqtrace.reset(capacity=32, topk=4)
        try:
            body = json.dumps({"prompt": [1, 2, 3], "max_new": 8,
                               "stream": True})
            sk = socket.create_connection((host, port), timeout=10)
            sk.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                          struct.pack("ii", 1, 0))  # close sends RST
            sk.sendall(("POST /v1/models/gen_http:generate HTTP/1.1\r\n"
                        "Host: t\r\nContent-Type: application/json\r\n"
                        "Content-Length: %d\r\n\r\n%s"
                        % (len(body), body)).encode())
            assert sk.recv(1)  # response started: the stream is live
            sk.close()
            rec, deadline = None, time.monotonic() + 20.0
            while time.monotonic() < deadline:
                snap = reqtrace.snapshot()
                done = [r for r in snap["recent"]
                        if r["model"] == "gen_http"]
                if done:
                    rec = done[0]
                    break
                time.sleep(0.05)
            assert rec is not None, "disconnected request never closed"
            assert rec["outcome"] == "cancelled"
            assert "client_disconnect" in rec["events"]
            assert rec["injected_any"]  # chaos stall never reads organic
            assert not snap["open"]
        finally:
            monkeypatch.delenv("MXNET_CHAOS")
            chaos.reset()
            reqtrace.reset()
    finally:
        fe.stop()
        srv.drain(timeout_s=10.0)
