"""Autoregressive generation serving: prefill/decode split over a
paged KV cache, with continuous (per-slot) batching.

This is the LM counterpart of the fixed-shape predictor tier
(runtime.py): the same AOT discipline — every compiled shape declared
in a bucket plan BEFORE traffic, warmup at load, zero steady-state
recompiles — applied to the two-phase shape problem generation poses:

  * **prefill** runs once per sequence over the whole prompt, compiled
    per bucketed ``(batch, prompt_len)``;
  * **decode** runs once per output token over ONE new token + the
    cache, compiled per bucketed ``(batch, cache_len)``.

Both plans are 2-D cross products from ``bucket_ladder``; each plan
cell gets its own ``diagnostics.instrument_jit`` wrapper, so "zero
steady-state recompiles" is a measured claim (every cell compiles
exactly once, at warmup — ``analysis.check_decode_buckets`` audits the
recorded avals against the declared plan).

The cache is paged (kvcache.py): a sequence holds a LIST of fixed-size
token blocks, its block table gathered INSIDE the compiled decode step
(``transformer.model.apply_decode``), so slot churn never copies or
compacts cache memory.  Nor does a step: the K and V pools are DONATED
to both compiled steps, which write their new rows into the buffers
they were handed and return them (no copy of a pool per tick).  The
dict that went into a step is dead afterwards; a step that fails after
it consumed the pools fails every live sequence and the pools are made
again (``GenerationEngine._recover_pools``).  Continuous batching
rides on top: a finished (or cancelled, or evicted) sequence's slot
and blocks are reclaimed on the NEXT decode tick and refilled from the
queue without draining the co-riding sequences.

What a block of the paged cache holds is the MODEL's to say
(``transformer.model.cache_rows``: K and V of every head, or a latent
mixer's compressed row), and a model with expert layers also carries
its routing counts beside the pools (``pages["routed"]``), added up on
the device by both compiled steps and read by
``GenerationRuntime.routing_counters()`` when asked, never in a tick.

Numerics contract, pinned by tests/test_zz_generate_e2e.py: greedy
decode
through this engine is token-for-token identical to running the plain
dense-cache reference forward (``model.apply`` with
``dense_causal_attn``) one sequence at a time.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import profiler as _profiler
from . import reqtrace as _reqtrace
from .batching import Request
from .bucket_ladder import bucket_for, ladder
from .errors import Cancelled, DeadlineExceeded, ExecutorFailure
from .kvcache import CacheExhausted, PagedKVCache

__all__ = ["GenRequest", "GenerationRuntime", "GenerationEngine",
           "demo_generation_runtime", "StubGenerationRuntime",
           "stub_greedy_reference"]

_log = logging.getLogger(__name__)


class GenRequest(Request):
    """One admitted generation request: the prompt, the output budget,
    per-token streaming (``on_token``) and timing (TTFT / TPOT), and a
    cancel flag the engine honors at its next decode tick."""

    __slots__ = ("prompt", "max_new", "on_token", "tokens",
                 "first_token_ts", "token_ts", "_cancelled")

    def __init__(self, model: str, prompt, max_new: int,
                 deadline_s: Optional[float] = None,
                 request_id: Optional[str] = None,
                 on_token: Optional[Callable[[Optional[int]], None]]
                 = None):
        import numpy as np

        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        super().__init__(model, prompt, 1, deadline_s=deadline_s,
                         request_id=request_id)
        self.prompt = prompt
        self.max_new = max(int(max_new), 1)
        #: called from the ENGINE thread with each generated token id,
        #: then once with None at end-of-stream (any outcome).  Must
        #: not block: a slow consumer stalls every co-riding sequence.
        self.on_token = on_token
        self.tokens: List[int] = []
        self.first_token_ts: Optional[float] = None
        self.token_ts: List[float] = []
        self._cancelled = threading.Event()

    def cancel(self) -> None:
        """Client disconnect / explicit abandon: the engine reclaims
        the slot and cache blocks at its next decode tick."""
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    # -- engine side ---------------------------------------------------
    def _emit(self, tok: int) -> None:
        now = time.monotonic()
        if self.first_token_ts is None:
            self.first_token_ts = now
        self.token_ts.append(now)
        self.tokens.append(int(tok))
        if self.on_token is not None:
            try:
                self.on_token(int(tok))
            except Exception:
                # a broken stream consumer becomes a cancel, never an
                # engine fault — co-riders must not feel it
                self._cancelled.set()

    def _close_stream(self) -> None:
        if self.on_token is not None:
            try:
                self.on_token(None)
            except Exception:
                pass

    def ttft_s(self) -> Optional[float]:
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.enqueue_ts

    def tpot_s(self) -> List[float]:
        """Per-output-token intervals (decode cadence; excludes the
        prefill-bound first token, which TTFT owns)."""
        return [b - a for a, b in zip(self.token_ts, self.token_ts[1:])]


class _Slot(object):
    __slots__ = ("req", "seq_id", "pos", "next_token", "decode_s",
                 "ticks")

    def __init__(self, req: GenRequest, seq_id: str, pos: int,
                 next_token: int):
        self.req = req
        self.seq_id = seq_id
        self.pos = int(pos)          # cache cursor: where next_token
        self.next_token = int(next_token)  # ...will be written
        # decode residency accumulates HERE (two float adds per tick)
        # and flushes to the request recorder once at retire — a
        # per-tick recorder call would dominate the recorder's cost
        self.decode_s = 0.0
        self.ticks = 0


class GenerationRuntime:
    """One served generator: transformer params + the 2-D bucket plans
    + one instrumented compiled callable per plan cell + the paged
    cache + the continuous-batching engine.  Presents the same surface
    ``ModelServer`` expects of a runtime (name/version/sample_shape/
    plan/compiled/compile/max_batch), so breakers, drain, probes, and
    live reload carry over unchanged."""

    def __init__(self, name: str, params: Dict, cfg, *,
                 slots: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 max_prompt: Optional[int] = None,
                 max_context: Optional[int] = None,
                 max_new: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_batch: Optional[int] = None,
                 source: str = "inline"):
        from .. import env as _env

        def knob(v, envname):
            return _env.get_int(envname) if v is None else int(v)

        self.name = str(name)
        self.version = 1
        self.source = source
        self.cfg = cfg
        self.slots = max(knob(slots, "MXNET_SERVE_GEN_SLOTS"), 1)
        self.block_tokens = max(
            knob(block_tokens, "MXNET_SERVE_KV_BLOCK_TOKENS"), 1)
        bt = self.block_tokens

        def round_up(n):
            return -(-int(n) // bt) * bt

        self.max_prompt = round_up(max(
            knob(max_prompt, "MXNET_SERVE_GEN_MAX_PROMPT"), 1))
        self.max_context = round_up(max(
            knob(max_context, "MXNET_SERVE_GEN_MAX_CONTEXT"),
            self.max_prompt))
        self.max_new = max(knob(max_new, "MXNET_SERVE_GEN_MAX_NEW"), 1)
        self.prefill_batch = min(
            max(knob(prefill_batch, "MXNET_SERVE_GEN_PREFILL_BATCH"), 1),
            self.slots)
        nb = knob(num_blocks, "MXNET_SERVE_GEN_BLOCKS")
        if nb <= 0:  # auto: every slot can hold a full context
            nb = self.slots * (self.max_context // bt) + 1
        #: ModelServer compatibility surface
        self.sample_shape = (self.max_prompt,)
        self.max_batch = self.slots
        # -- the four ladders -> two 2-D plans ------------------------
        self.batch_plan = ladder(self.slots)
        self.cache_plan = tuple(
            b * bt for b in ladder(self.max_context // bt))
        self.prompt_plan = tuple(
            b * bt for b in ladder(self.max_prompt // bt))
        self.prefill_plan: Tuple[Tuple[int, int], ...] = tuple(
            (a, b) for a in ladder(self.prefill_batch)
            for b in self.prompt_plan)
        self.decode_plan: Tuple[Tuple[int, int], ...] = tuple(
            (a, b) for a in self.batch_plan for b in self.cache_plan)
        self.plan = self.decode_plan  # what stats()/dashboards show
        self._params = self._to_device(params)
        rows, counters = self._cache_layout()
        self.kv = PagedKVCache(
            rows=rows, counters=counters, num_blocks=nb, block_tokens=bt,
            dtype=cfg.dtype, name=self.name)
        # ``routed`` as last read (it wraps at 2**32: the differences
        # do not), and the pools' generation it was read from
        self._routed_seen = None
        #: one instrumented wrapper per plan cell — "zero steady-state
        #: recompiles" means every wrapper's compile count stays at its
        #: warmup value of exactly 1
        self._prefill: Dict[Tuple[int, int], Any] = {}
        self._decode: Dict[Tuple[int, int], Any] = {}
        self._compile_ms: Dict[str, float] = {}
        self._lock = threading.Lock()
        self.engine = GenerationEngine(self)

    def _to_device(self, params):
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(jnp.asarray, params)

    def _cache_layout(self):
        """``(rows, counters)`` of the paged cache, as the model states
        them: the row a token leaves in each pool, and the int32 arrays
        that ride beside the pools."""
        from ..transformer import model as _model

        routed = _model.routed_shape(self.cfg)
        return (_model.cache_rows(self.cfg),
                {"routed": routed} if routed else {})

    def routing_counters(self) -> Optional[Dict]:
        """What the expert layers routed since the last call
        (``transformer.model.routed_counts``), also stamped as profiler
        counters ``moe.*``; None for a model without expert layers.
        ONE read of the small array that both compiled steps add to in
        place on the device, taken between two steps of the engine
        (``kv.in_step``: it waits for a step in flight to hand the
        pages back): call it outside a timed window."""
        import numpy as np

        from ..transformer import model as _model

        if "routed" not in self.kv.pages:
            return None
        with self.kv.in_step:
            now = np.asarray(  # mxlint: disable=MXL004
                self.kv.pages["routed"]).astype(np.uint32)
            gen = self.kv.pool_rebuilds
        if self._routed_seen is None or self._routed_seen[0] != gen:
            self._routed_seen = (gen, np.zeros_like(now))
        delta = (now - self._routed_seen[1]).astype(np.int64)
        self._routed_seen = (gen, now)
        out = _model.routed_counts(delta, self.cfg)
        for k in ("assignments_total", "assignments_here", "dropped",
                  "load_max_over_mean", "experts_reached"):
            _profiler.record_counter("moe." + k, out[k])
        return out

    # -- compilation ---------------------------------------------------
    @property
    def compiled(self) -> bool:
        return (len(self._prefill) == len(self.prefill_plan)
                and len(self._decode) == len(self.decode_plan))

    def _jit_fns(self):
        import jax
        import jax.numpy as jnp

        from ..transformer import model as _model

        cfg, bt = self.cfg, self.block_tokens

        # each step chooses its tokens where the logits are: the host
        # reads back ``bb`` int32 ids, not ``bb`` rows of the vocabulary
        # in float32 (``argmax`` takes the first maximum, as numpy's)
        def greedy(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def prefill_fn(params, tokens, prompt_lens, pages,
                       block_tables):
            logits, pages = _model.apply_prefill(
                params, tokens, prompt_lens, cfg, pages=pages,
                block_tables=block_tables, block_tokens=bt)
            return greedy(logits), pages

        def decode_fn(params, tokens, positions, pages, block_tables):
            logits, pages = _model.apply_decode(
                params, tokens, positions, cfg, pages=pages,
                block_tables=block_tables, block_tokens=bt)
            return greedy(logits), pages

        # ``pages`` (argument 3) is donated: each step scatters its
        # rows into the pools it was handed, where an undonated pool
        # would be copied whole before every write
        return (jax.jit(prefill_fn, donate_argnums=(3,)),
                jax.jit(decode_fn, donate_argnums=(3,)))

    def _stamp_traces(self, prefill, decode) -> int:
        """What the traces of both compiled steps say (each trace is
        the one its cell's first call reuses), ``(step, args)`` each:
        ``kv.pools_donated`` of ``kv.pools``, the pools that both take
        donated (off ``args_info``, as ``analysis.check_donation``
        reads it), over the pools there are; and ``gen.readback_bytes``,
        what the decode cell hands the host beside the pools (off
        ``out_info``).  A step that is no jit donates nothing and
        stamps no readback.  Returns the pools donated."""
        pools = set(self.kv.pools)
        donated = set(pools)
        for step, args in (prefill, decode):
            trace = getattr(step, "trace", None)
            traced = trace(*args) if trace else None
            info = traced.args_info[0][3] if traced else {}
            donated &= {k for k, a in info.items() if a.donated}
        if traced:                      # the decode step's
            ids = traced.out_info[0]
            _profiler.record_counter("gen.readback_bytes",
                                     ids.size * ids.dtype.itemsize)
        _profiler.record_counter("kv.pools_donated", len(donated))
        _profiler.record_counter("kv.pools", len(pools))
        return len(donated)

    def compile(self, warmup: bool = True) -> Dict[str, float]:
        """Compile + warm every cell of BOTH plans, one instrumented
        wrapper per cell, so the first request pays neither compile nor
        first-dispatch cost and the recompile registry starts at
        exactly one compile per cell.  Idempotent."""
        import jax
        import numpy as np

        from .. import diagnostics as _diag
        from ..compile_cache import enable as _cc_enable
        from ..parallel import attention as _attention
        from ..transformer import paged_latent as _paged

        _cc_enable()
        with self._lock:
            if self.compiled:
                return dict(self._compile_ms)
            pjit, djit = self._jit_fns()
            bt = self.block_tokens
            meta = {"model": self.name,
                    "block_tokens": bt,
                    "decode_plan": [list(c) for c in self.decode_plan]}

            def ints(*shape):
                return np.zeros(shape, dtype=np.int32)

            # a cell's warm-up arguments, made when the cell is warmed:
            # the pools are whatever the step before gave back
            def prefill_args(bb, tb):
                return (self._params, ints(bb, tb), ints(bb),
                        self.kv.pages, ints(bb, tb // bt))

            def decode_args(bb, lb):
                return (self._params, ints(bb), ints(bb),
                        self.kv.pages, ints(bb, lb // bt))

            sites = _attention.site_tally()
            decode_sites = _paged.site_tally()
            donated = self._stamp_traces(
                (pjit, prefill_args(*self.prefill_plan[0])),
                (djit, decode_args(*max(self.decode_plan))))
            for how, n in _attention.site_tally(sites).items():
                # the dense block's explicit mask is no site: it stamps
                # nothing, as before
                if n:
                    _profiler.record_counter("attn.%s_sites" % how, n)
            # the latent decode's attention, which only the decode step
            # holds: the paged kernel's sites and the gather's
            for how, n in _paged.site_tally(decode_sites).items():
                _profiler.record_counter("attn.decode_%s_sites" % how, n)
            for kind, plan, cells, fn, args_of in (
                    ("prefill", self.prefill_plan, self._prefill, pjit,
                     prefill_args),
                    ("decode", self.decode_plan, self._decode, djit,
                     decode_args)):
                for key in plan:
                    if key in cells:
                        continue
                    nm = "gen_%s:%s:v%d:%dx%d" % (
                        (kind, self.name, self.version) + key)
                    w = _diag.instrument_jit(
                        nm, fn, meta=dict(meta, kind="generate_" + kind))
                    t0 = time.perf_counter()
                    if warmup:
                        ids, pages = w(*args_of(*key))
                        jax.block_until_ready(ids)  # mxlint: disable=MXL004
                        self.kv.pages = pages
                    self._compile_ms[nm] = (time.perf_counter() - t0) * 1e3
                    cells[key] = w
                    self._feed_compile_metrics(self._compile_ms[nm])
            _log.info(
                "serving: compiled generator %r — %d prefill + %d "
                "decode plan cells (warmup=%s), %d of %d KV pools "
                "donated", self.name, len(self._prefill),
                len(self._decode), warmup, donated, len(self.kv.pools))
            return dict(self._compile_ms)

    def _feed_compile_metrics(self, dur_ms: float) -> None:
        try:
            from .. import diagnostics as _diag

            _diag.metrics.counter(
                "mxnet_serve_compiles_total",
                help="AOT-compiled serving executors",
                labels={"model": self.name}).inc()
            _diag.metrics.gauge(
                "mxnet_serve_compile_ms_last",
                labels={"model": self.name}).set(dur_ms)
        except Exception:
            pass

    def compile_stats(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._compile_ms)

    # -- reload support ------------------------------------------------
    def successor_from_checkpoint(self, directory: str,
                                  step: Optional[int] = None
                                  ) -> "GenerationRuntime":
        """A NEW version of this generator from a (verified)
        checkpoint: same config, plans, and cache geometry — only the
        weights change (what :meth:`ModelServer.reload` canaries)."""
        from .. import checkpoint as _ckpt

        payload = _ckpt.load_checkpoint(directory, step=step)
        params = payload.get("params") or {}
        if not params:
            raise ValueError(
                "checkpoint step %s under %r holds no params"
                % (payload.get("step"), directory))
        return type(self)(
            self.name, params, self.cfg, slots=self.slots,
            block_tokens=self.block_tokens, max_prompt=self.max_prompt,
            max_context=self.max_context, max_new=self.max_new,
            num_blocks=self.kv.num_blocks,
            prefill_batch=self.prefill_batch,
            source="checkpoint:%s@step%s" % (directory,
                                             payload.get("step")))


class GenerationEngine:
    """The continuous batcher: a waiting line, up to ``slots`` active
    sequences, and a tick loop — reap (cancel/expire/evict), admit
    (batched prefill), decode (one token for every rider).  All engine
    state is touched from ONE worker thread (``ModelServer`` owns it);
    requests/cancel flags are the thread-safe crossings.

    A step's host time is put down in the profiler's ring by phase, a
    few records a compiled call whatever the number of riders:
    ``mx.engine.prepare`` (from the step's entry, or from the end of the
    prefill's streaming, to the call), the call's own ``mx.prefill`` /
    ``mx.tick`` span, ``mx.prefill.readback`` / ``mx.tick.readback``
    inside it (the wait for the run, then its ``bb`` int32 ids to the
    host), and
    ``mx.engine.stream`` (the tokens to their callers, the retirements,
    up to the next call's preparation or the step's return)."""

    def __init__(self, runtime: GenerationRuntime):
        self.rt = runtime
        self.kv = runtime.kv
        self.active: List[_Slot] = []
        self.waiting: "deque[GenRequest]" = deque()
        self.ticks = 0
        self.tokens_out = 0
        # ``perf_counter()`` where the step's current host phase began,
        # and whether it streams a call's tokens (else it prepares one)
        self._host_t0 = 0.0
        self._streaming = False
        # stable physical slot indices (not positions in ``active``):
        # the reqtrace slot timeline needs one lane per slot, and a
        # retiring co-rider must not renumber everyone behind it
        self._slot_idx: Dict[str, int] = {}
        self._free_idx: List[int] = list(range(runtime.slots))
        _reqtrace.set_slots(runtime.name, runtime.slots)

    def _slot_on(self, seq_id: str) -> None:
        idx = self._free_idx.pop(0) if self._free_idx \
            else len(self._slot_idx)
        self._slot_idx[seq_id] = idx
        _reqtrace.slot_acquire(self.rt.name, idx, seq_id)

    def _slot_off(self, seq_id: str) -> None:
        idx = self._slot_idx.pop(seq_id, None)
        if idx is not None:
            self._free_idx.append(idx)
            self._free_idx.sort()
            _reqtrace.slot_release(self.rt.name, idx)

    # -- server-facing surface ----------------------------------------
    def enqueue(self, req: GenRequest) -> None:
        self.waiting.append(req)

    def free_slots(self) -> int:
        return max(self.rt.slots - len(self.active) - len(self.waiting),
                   0)

    def idle(self) -> bool:
        return not self.active and not self.waiting

    def abort_all(self, make_error) -> List[tuple]:
        """Fail every waiting + active sequence (rollback of a canary
        engine; breaker-trip flush).  Returns the outcome tuples."""
        outcomes = []
        for req in list(self.waiting):
            self._finish(req, "error", make_error(req))
            outcomes.append((req, "error", None))
        self.waiting.clear()
        for s in list(self.active):
            self.kv.free(s.seq_id)
            self._slot_off(s.seq_id)
            self._flush_trace(s)
            self._finish(s.req, "error", make_error(s.req))
            outcomes.append((s.req, "error", None))
        self.active = []
        return outcomes

    # -- one engine tick ----------------------------------------------
    def step(self, is_canary: bool = False) -> Dict[str, Any]:
        """Reap, admit, decode — one tick.  Returns {outcomes:
        [(req, outcome, exc)], ticked, exec_error, tokens}."""
        rep: Dict[str, Any] = {"outcomes": [], "ticked": False,
                               "exec_error": None, "tokens": 0}
        self.ticks += 1
        self._host_t0, self._streaming = time.perf_counter(), False
        self._reap(rep)
        try:
            self._admit(rep)
            self._decode(rep, is_canary)
        except ExecutorFailure as e:
            rep["exec_error"] = e
        self.kv.feed_metrics()
        if self._streaming:
            self._host_phase("mx.engine.stream", time.perf_counter())
        return rep

    def _host_phase(self, name: str, t1: float,
                    depth: Optional[int] = None) -> None:
        """Record the host phase that began at ``_host_t0`` as ending at
        ``t1``, where the next one begins."""
        _profiler.record_interval(name, self._host_t0, t1, cat="serving",
                                  depth=depth)
        self._host_t0 = t1

    def _called(self, call, ids, what: str):
        """Inside a compiled call's span, once it has returned: its
        preparation recorded beside it, the tokens it chose read back
        (which waits for the run) and recorded inside it; the tokens."""
        import numpy as np

        self._host_phase("mx.engine.prepare", call.t0, depth=call.depth)
        t0 = time.perf_counter()
        out = np.asarray(ids)  # mxlint: disable=MXL004
        _profiler.record_interval(what + ".readback", t0,
                                  time.perf_counter(), cat="serving")
        return out

    def _finish(self, req: GenRequest, outcome: str,
                error: Optional[BaseException] = None) -> None:
        if not req.done():
            if error is None:
                req.set_result({"tokens": list(req.tokens),
                                "prompt_len": len(req.prompt)})
            else:
                req.set_error(error)
        req._close_stream()

    def _flush_trace(self, slot: _Slot) -> None:
        """Fold the slot's accumulated decode residency into the
        request's trace — must run before the terminal set_result/
        set_error pops the open record."""
        if slot.ticks:
            _reqtrace.phase(slot.req.id, "decode", slot.decode_s)
            _reqtrace.event(slot.req.id, "decode_ticks", n=slot.ticks)
            slot.decode_s, slot.ticks = 0.0, 0

    def _retire(self, rep, slot: _Slot, outcome: str,
                error: Optional[BaseException] = None,
                evicted: bool = False) -> None:
        self.kv.free(slot.seq_id, evicted=evicted)
        self._slot_off(slot.seq_id)
        self._flush_trace(slot)
        self._finish(slot.req, outcome, error)
        rep["outcomes"].append((slot.req, outcome, error))

    def _reap(self, rep) -> None:
        """Cancellations (client or chaos ``cancel_request``), deadline
        expiry — slot + blocks reclaimed NOW, co-riders untouched."""
        from .. import chaos as _chaos

        now = time.monotonic()
        keep_w: "deque[GenRequest]" = deque()
        for req in self.waiting:
            if req.cancelled:
                _reqtrace.phase(req.id, "queue", now - req.enqueue_ts)
                self._finish(req, "cancelled", Cancelled(
                    "request %s cancelled while waiting" % req.id))
                rep["outcomes"].append((req, "cancelled", None))
            elif req.expired(now):
                # the whole life was queue residency: make the autopsy
                # say "died waiting", not just "expired"
                _reqtrace.phase(req.id, "queue", now - req.enqueue_ts)
                self._finish(req, "expired", DeadlineExceeded(
                    "request %s: deadline expired before a slot freed"
                    % req.id))
                rep["outcomes"].append((req, "expired", None))
            else:
                keep_w.append(req)
        self.waiting = keep_w
        chaos_on = _chaos.enabled()
        keep: List[_Slot] = []
        for s in self.active:
            if chaos_on and _chaos.should_cancel_request(self.rt.name):
                s.req.cancel()
            if s.req.cancelled:
                self._retire(rep, s, "cancelled", Cancelled(
                    "request %s cancelled mid-stream after %d tokens"
                    % (s.req.id, len(s.req.tokens))))
            elif s.req.expired(now):
                self._retire(rep, s, "expired", DeadlineExceeded(
                    "request %s: deadline expired mid-generation "
                    "(%d tokens out)" % (s.req.id, len(s.req.tokens))))
            else:
                keep.append(s)
        self.active = keep

    def _admit(self, rep) -> None:
        """Batched prefill for up to ``prefill_batch`` waiting
        sequences.  Cache-exhausted admissions stay waiting; their
        deadline keeps running."""
        import numpy as np

        from .. import chaos as _chaos

        rt = self.rt
        room = rt.slots - len(self.active)
        group: List[GenRequest] = []
        seqs: List[str] = []
        admit_t = time.monotonic()
        while self.waiting and len(group) < min(room, rt.prefill_batch):
            req = self.waiting[0]
            seq_id = req.id
            try:
                self.kv.alloc(seq_id, len(req.prompt))
            except CacheExhausted:
                # admitted-blocked: start (or keep) the wait marker so
                # "Nms waiting on CacheExhausted" is a traced phase
                _reqtrace.cache_wait(req.id)
                break  # blocks free as riders finish; stay waiting
            self.waiting.popleft()
            _reqtrace.phase(req.id, "queue", admit_t - req.enqueue_ts)
            group.append(req)
            seqs.append(seq_id)
        if not group:
            return
        prefill_t0 = time.monotonic()
        try:
            if _chaos.enabled() and \
                    _chaos.should_fail_execute(rt.name):
                raise ExecutorFailure(
                    "chaos fail_execute injected for generator %r"
                    % rt.name)
            bb = bucket_for([a for a, _ in rt.prefill_plan],
                            len(group))
            tb = bucket_for(rt.prompt_plan,
                            max(len(r.prompt) for r in group))
            bt = rt.block_tokens
            tokens = np.zeros((bb, tb), dtype=np.int32)
            plens = np.ones((bb,), dtype=np.int32)
            tables = np.zeros((bb, tb // bt), dtype=np.int32)
            for i, req in enumerate(group):
                p = len(req.prompt)
                tokens[i, :p] = req.prompt
                plens[i] = p
                tables[i] = self.kv.block_table(seqs[i], tb // bt)
            w = rt._prefill[(bb, tb)]
            with self.kv.in_step, _profiler.span(
                    "mx.prefill", cat="serving", args={
                        "tokens": int(plens[:len(group)].sum())}) as call:
                ids, pages = w(rt._params, tokens, plens,
                               self.kv.pages, tables)
                first = self._called(call, ids, "mx.prefill")
                # kept once the ids are read: a run that fails on
                # the device raises there, with pools as dead as those
                # it was handed
                self.kv.pages = pages
            self._host_t0, self._streaming = time.perf_counter(), True
        except Exception as e:
            err = e if isinstance(e, ExecutorFailure) else \
                ExecutorFailure("prefill for %r failed: %r"
                                % (rt.name, e))
            for req, seq_id in zip(group, seqs):
                self.kv.free(seq_id)
                self._finish(req, "error", err)
                rep["outcomes"].append((req, "error", err))
            self._recover_pools(rep, err)
            raise err
        rep["ticked"] = True
        prefill_dur = time.monotonic() - prefill_t0
        rider_ids = [r.id for r in group]
        for i, req in enumerate(group):
            _reqtrace.phase(req.id, "prefill", prefill_dur,
                            bucket="%dx%d" % (bb, tb))
            _reqtrace.event(req.id, "batch_formed",
                            bucket="%dx%d" % (bb, tb),
                            co_riders=[r for r in rider_ids
                                       if r != req.id])
            tok = int(first[i])
            req._emit(tok)
            rep["tokens"] += 1
            self.tokens_out += 1
            slot = _Slot(req, seqs[i], pos=len(req.prompt),
                         next_token=tok)
            self._slot_on(seqs[i])
            if len(req.tokens) >= req.max_new:
                self._retire(rep, slot, "ok")
            else:
                self.active.append(slot)

    def _decode(self, rep, is_canary: bool) -> None:
        """One decode tick for every rider: grow cache coverage (a
        sequence that cannot get its next block is EVICTED, counted),
        pick the (batch, cache_len) plan cell, run the compiled step,
        stream the new tokens, retire the finished."""
        import numpy as np

        from .. import chaos as _chaos

        rt = self.rt
        if not self.active:
            return
        if self._streaming:
            self._host_phase("mx.engine.stream", time.perf_counter())
            self._streaming = False
        riders: List[_Slot] = []
        for s in self.active:
            try:
                self.kv.extend(s.seq_id, s.pos + 1)
                riders.append(s)
            except CacheExhausted as e:
                self._retire(rep, s, "error", ExecutorFailure(
                    "sequence %s evicted under cache pressure: %r"
                    % (s.req.id, e)), evicted=True)
        self.active = riders
        if not riders:
            return
        trace_on = _reqtrace.recorder.enabled
        tick_t0 = time.monotonic() if trace_on else 0.0
        injected = None
        if _chaos.enabled():
            if _chaos.should_fail_execute(rt.name):
                raise self._fail_riders(rep, ExecutorFailure(
                    "chaos fail_execute injected for generator %r"
                    % rt.name))
            if is_canary and _chaos.should_fail_version(
                    rt.name, rt.version):
                raise self._fail_riders(rep, ExecutorFailure(
                    "chaos bad_version injected for %r v%d"
                    % (rt.name, rt.version)))
            # a seeded tick stall sleeps HERE (inside the measured
            # tick) and comes back tagged, so the autopsy pins it on
            # chaos rather than an organically slow decode step
            injected = _chaos.maybe_stall_decode_tick(rt.name)
        bb = bucket_for(rt.batch_plan, len(riders))
        need = max(s.pos + 1 for s in riders)
        lb = bucket_for(rt.cache_plan, need)
        bt = rt.block_tokens
        tokens = np.zeros((bb,), dtype=np.int32)
        positions = np.zeros((bb,), dtype=np.int32)
        tables = np.zeros((bb, lb // bt), dtype=np.int32)
        for i, s in enumerate(riders):
            tokens[i] = s.next_token
            positions[i] = s.pos
            tables[i] = self.kv.block_table(s.seq_id, lb // bt)
        try:
            w = rt._decode[(bb, lb)]
            with self.kv.in_step, _profiler.span(
                    "mx.tick", cat="serving", args={
                        "live": len(riders), "slots": rt.slots}) as call:
                ids, pages = w(rt._params, tokens, positions,
                               self.kv.pages, tables)
                nxt = self._called(call, ids, "mx.tick")
                self.kv.pages = pages  # once read: see _admit
        except Exception as e:
            err = self._fail_riders(rep, ExecutorFailure(
                "decode tick for %r (bucket %dx%d) failed: %r"
                % (rt.name, bb, lb, e)))
            self._recover_pools(rep, err)
            raise err
        self._host_t0, self._streaming = time.perf_counter(), True
        rep["ticked"] = True
        if trace_on:
            tick_dur = time.monotonic() - tick_t0
            if injected is not None:
                _reqtrace.tick(rt.name, tick_dur,
                               [s.req.id for s in riders],
                               injected=injected)
            else:
                for s in riders:
                    s.decode_s += tick_dur
                    s.ticks += 1
        keep: List[_Slot] = []
        for i, s in enumerate(riders):
            tok = int(nxt[i])
            s.req._emit(tok)
            rep["tokens"] += 1
            self.tokens_out += 1
            s.pos += 1
            s.next_token = tok
            self.kv.note_length(s.seq_id, s.pos)
            if len(s.req.tokens) >= s.req.max_new:
                self._retire(rep, s, "ok")
            else:
                keep.append(s)
        self.active = keep

    def _fail_riders(self, rep, err: ExecutorFailure) -> ExecutorFailure:
        """Decode-tick failure: every rider rode the failed batch —
        error them all, free their blocks, return the error for the
        caller to raise (the breaker's food)."""
        for s in self.active:
            self.kv.free(s.seq_id)
            self._slot_off(s.seq_id)
            self._flush_trace(s)
            self._finish(s.req, "error", err)
            rep["outcomes"].append((s.req, "error", err))
        self.active = []
        return err

    def _recover_pools(self, rep, err: ExecutorFailure) -> None:
        """After any exception from a compiled step.  The pools are
        donated, so a step that raised once it had been dispatched has
        consumed them (``kv.pools_lost()``: an array of ``kv.pages``
        is deleted) and every live sequence's history with them: fail
        every rider, the prefill path's too, free their blocks and
        make the pools again, so the breaker's next probe finds a
        cache that works.  A step that raised before that (the chaos
        injections, a tracing or argument error) leaves the pools
        whole, and the riders keep streaming."""
        if not self.kv.pools_lost():
            return
        _log.warning(
            "serving: a failed step of %r consumed the KV pools — "
            "failing %d live sequence(s) and rebuilding the pools",
            self.rt.name, len(self.active))
        self._fail_riders(rep, err)
        self.kv.rebuild_pools()


def demo_generation_runtime(name: str = "gen", seed: int = 0, *,
                            vocab: int = 64, n_layers: int = 2,
                            d_model: int = 32, n_heads: int = 2,
                            **kw) -> GenerationRuntime:
    """A tiny fixed-seed transformer generator — the self-test /
    loadgen / bench model (real enough to prefill, page, decode, and
    stream like production)."""
    import jax

    from ..transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=vocab, n_layers=n_layers,
                            d_model=d_model, n_heads=n_heads,
                            d_ff=2 * d_model)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return GenerationRuntime(name, params, cfg, **kw)


class _StubGenConfig:
    """Minimal config surface StubGenerationRuntime needs (vocab for
    the arithmetic token rule, the cache's dtype)."""

    vocab_size = 64
    dtype = "float32"


def stub_greedy_reference(prompt, n_new: int, vocab: int = 64):
    """The dense reference for :class:`StubGenerationRuntime`'s token
    rule: ``next = sum(history) % vocab`` over the raw token ids."""
    hist = [int(t) for t in prompt]
    out: List[int] = []
    for _ in range(n_new):
        nxt = sum(hist) % int(vocab)
        out.append(nxt)
        hist.append(nxt)
    return out


class StubGenerationRuntime(GenerationRuntime):
    """Host-only generator for the self-tests: the REAL engine, plans,
    paged allocator, and instrumented per-cell dispatch — but each
    "compiled" cell is a numpy function that scatters the new tokens
    into the pages and gathers the history back THROUGH THE BLOCK
    TABLE (``next = sum(gathered history) % vocab``).  A broken
    allocator, table, or garbage-block contract therefore diverges
    from :func:`stub_greedy_reference` exactly like a broken kernel
    would — in milliseconds, with zero XLA compiles.  The real-model
    numerics pins live in tests/test_zz_generate_e2e.py."""

    def __init__(self, name: str, **kw):
        super().__init__(name, {}, _StubGenConfig(), **kw)

    def _to_device(self, params):
        return params  # host stub: nothing to place on a device

    def _cache_layout(self):
        return {"k0": (1, 1), "v0": (1, 1)}, {}

    def _jit_fns(self):
        import numpy as np

        bt, vocab = self.block_tokens, self.cfg.vocab_size

        def _np_pages(pages):
            if isinstance(pages["k0"], np.ndarray):
                return pages
            # first call: copy the (tiny) zero pools off the device
            # once (np.asarray views of jax arrays are read-only);
            # afterwards the pages stay host arrays
            return {k: np.array(v) for k, v in pages.items()}

        # each returns the ``bb`` int32 ids, as the compiled steps do
        def prefill_fn(params, tokens, prompt_lens, pages, tables):
            pages = _np_pages(pages)
            k = pages["k0"]
            bb = int(tokens.shape[0])
            ids = np.zeros((bb,), dtype=np.int32)
            for i in range(bb):
                p = int(prompt_lens[i])
                for j in range(p):
                    k[tables[i, j // bt], j % bt, 0, 0] = tokens[i, j]
                hist = k[tables[i], :, 0, 0].reshape(-1)[:p]
                ids[i] = int(hist.sum()) % vocab
            k[0] = 0.0  # padded rows wrote here; garbage stays garbage
            return ids, pages

        def decode_fn(params, tokens, positions, pages, tables):
            pages = _np_pages(pages)
            k = pages["k0"]
            bb = int(tokens.shape[0])
            ids = np.zeros((bb,), dtype=np.int32)
            for i in range(bb):
                pos = int(positions[i])
                k[tables[i, pos // bt], pos % bt, 0, 0] = tokens[i]
                hist = k[tables[i], :, 0, 0].reshape(-1)[:pos + 1]
                ids[i] = int(hist.sum()) % vocab
            k[0] = 0.0
            return ids, pages

        return prefill_fn, decode_fn
