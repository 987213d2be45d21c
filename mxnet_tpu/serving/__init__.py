"""mx.serving — the production inference tier: a dynamic-batching
model server built robustness-first on the checkpoint / diagnostics /
chaos stack.

The amalgamation + c_predict ABI proved python-free single-shape
inference; this package is what actually fronts traffic: per-model
bounded queues with admission control and explicit load shedding,
deadline propagation (expired work is never batched), AOT-compiled
bf16 executors per batch bucket with a warmup pass (the first request
never pays compile latency), a per-model circuit breaker, graceful
SIGTERM drain through the shared preemption-hook path (exit 83 — see
the README exit-code table), distinct liveness/readiness probes,
zero-downtime model reload with a canary phase and auto-rollback
(``ModelServer.reload``: digest-verified load -> compile+warm ->
canary ``MXNET_SERVE_CANARY_PCT``% of traffic -> promote or roll back
on error-rate regression, zero admitted requests dropped), and
Prometheus metrics (p50/p99 latency, QPS, queue depth, shed counts,
per-version outcome counters) through ``diagnostics.metrics``.

Quickstart::

    from mxnet_tpu import serving

    rt = serving.ModelRuntime.from_checkpoint(
        "resnet", "/ckpts/resnet", apply_fn, sample_shape=(3, 224, 224))
    srv = serving.ModelServer()
    srv.add_model(rt)                     # compiles + warms every bucket
    srv.install_preemption_hook()         # SIGTERM -> drain -> exit 83
    out = srv.predict("resnet", batch, deadline_ms=250)

The GENERATION tier (serving/generate.py) extends the same machinery
to autoregressive decode: prefill/decode split with 2-D bucket-ladder
plans (zero steady-state recompiles, instrument_jit-verified), a paged
KV-cache allocator (kvcache.py — fixed token blocks, free list, block
tables gathered inside the compiled step), continuous per-slot
batching (a finished sequence's slot refills next tick without
draining co-riders), token streaming over chunked HTTP, and TTFT/TPOT
SLO load generation::

    grt = serving.demo_generation_runtime("gen")
    srv.add_generator(grt)                # warms every plan cell
    req = srv.submit_generation("gen", prompt_ids, max_new=16,
                                on_token=print)   # or srv.generate(..)
    tokens = req.wait(30.0)["tokens"]     # req.cancel() mid-stream ok

``python -m mxnet_tpu.serving --self-test`` exercises admission,
deadline expiry, breaker trip/reset, drain ordering, and the
generation tier (decode equality, continuous batching, streaming,
cancel reclaim) — tier-1 via tests/test_serving.py; ``--serve`` runs
the HTTP front-end.

Per-request observability (serving/reqtrace.py): every request's
lifecycle is recorded as monotonic-clock spans into a ring
(``MXNET_SERVE_REQTRACE_SIZE``; 0 disables), with a sliding-window
tail-latency autopsy (``reqtrace.dump()`` / SIGUSR1 / blown
deadlines), a per-slot occupancy timeline merge_traces.py renders,
and worst-sample exemplars in /stats and the prom exposition.
"""
from . import reqtrace
from .batching import Request, RequestQueue
from .bucket_ladder import (bucket_for, bucket_for_2d, ladder,
                            ladder_2d)
from .errors import (REJECT_REASONS, Cancelled, DeadlineExceeded,
                     ExecutorFailure, Rejected, ServeError)
from .generate import (GenerationEngine, GenerationRuntime, GenRequest,
                       StubGenerationRuntime, demo_generation_runtime,
                       stub_greedy_reference)
from .http import HttpFrontend
from .kvcache import CacheExhausted, PagedKVCache
from .loadgen import BackgroundLoad, qps_at_slo, run_load
from .runtime import (ModelRuntime, demo_params, demo_runtime,
                      plan_batch_buckets)
from .server import CircuitBreaker, ModelServer

__all__ = [
    "Request", "RequestQueue", "ServeError", "Rejected",
    "DeadlineExceeded", "ExecutorFailure", "Cancelled",
    "REJECT_REASONS",
    "ModelRuntime", "demo_runtime", "demo_params",
    "plan_batch_buckets",
    "ladder", "ladder_2d", "bucket_for", "bucket_for_2d",
    "PagedKVCache", "CacheExhausted",
    "GenRequest", "GenerationRuntime", "GenerationEngine",
    "demo_generation_runtime", "StubGenerationRuntime",
    "stub_greedy_reference",
    "CircuitBreaker", "ModelServer", "HttpFrontend",
    "run_load", "qps_at_slo", "BackgroundLoad",
    "reqtrace",
]
