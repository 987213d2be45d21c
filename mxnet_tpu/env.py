"""mx.env — central registry of ``MXNET_*`` environment knobs.

The reference configured ~40 runtime knobs through scattered
``dmlc::GetEnv`` calls (SURVEY.md §5); this rebuild had grown the same
sprawl (buckets.py, diagnostics.py, profiler.py, remat.py, engine.py,
_ps.py, ...), each site re-implementing parsing, defaults and
truthiness.  This module is the ONE declaration site: every knob is
registered here with its name, type, default and one-line doc, and
every read goes through the typed accessors below.

Why it matters beyond tidiness:

  * ``tools/mxlint.py`` statically rejects reads of UNREGISTERED
    ``MXNET_*`` names anywhere in ``mxnet_tpu/`` (a typo'd knob
    silently falling back to its default is a config bug that costs a
    cluster run to notice);
  * registrations marked ``import_time=True`` document the few knobs
    that are legitimately consumed while the package imports
    (profiler autostart); everything else must be read lazily so
    ``os.environ`` changes after import (tests, launchers that set env
    per worker) keep working — mxlint flags module-level reads;
  * :func:`describe` renders the registry as the canonical knob table
    for docs and ``--help`` surfaces.

Truthiness contract for ``bool`` knobs (shared with the flight
recorder's dump flag): ``0/false/no/off`` (any case) are False,
anything else set is True; unset/empty falls back to the registered
default — consistent with every other accessor.
"""
from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple, Optional

__all__ = [
    "EnvVar", "register", "registered", "is_registered", "var",
    "get_raw", "get_str", "get_int", "get_float", "get_bool",
    "describe",
]

_FALSE_SPELLINGS = ("0", "false", "no", "off")


class EnvVar(NamedTuple):
    """One registered knob: declaration == documentation."""
    name: str
    kind: str          # 'int' | 'float' | 'bool' | 'str'
    default: Any
    doc: str
    import_time: bool = False  # consumed at package import by design


_REGISTRY: Dict[str, EnvVar] = {}


def register(name: str, kind: str, default: Any, doc: str,
             import_time: bool = False) -> EnvVar:
    if kind not in ("int", "float", "bool", "str"):
        raise ValueError("unknown env kind %r for %s" % (kind, name))
    v = EnvVar(name, kind, default, doc, import_time)
    _REGISTRY[name] = v
    return v


def registered() -> Dict[str, EnvVar]:
    return dict(_REGISTRY)


def is_registered(name: str) -> bool:
    return name in _REGISTRY


def var(name: str) -> EnvVar:
    v = _REGISTRY.get(name)
    if v is None:
        raise KeyError(
            "environment variable %r is not registered in mxnet_tpu.env "
            "— declare it there (one line: name, type, default, doc) "
            "before reading it" % name)
    return v


_UNSET = object()


def get_raw(name: str) -> Optional[str]:
    """The raw environment string for a REGISTERED name (None if
    unset).  Callers needing custom parsing (the flight recorder's
    bool-or-path dump flag) start here."""
    var(name)
    return os.environ.get(name)


def get_str(name: str, default: Any = _UNSET) -> Optional[str]:
    v = var(name)
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return v.default if default is _UNSET else default
    return raw


def get_int(name: str, default: Any = _UNSET) -> Optional[int]:
    v = var(name)
    fallback = v.default if default is _UNSET else default
    raw = os.environ.get(name)
    if raw in (None, ""):
        return fallback
    try:
        return int(raw)
    except ValueError:
        return fallback


def get_float(name: str, default: Any = _UNSET) -> Optional[float]:
    v = var(name)
    fallback = v.default if default is _UNSET else default
    raw = os.environ.get(name)
    if raw in (None, ""):
        return fallback
    try:
        return float(raw)
    except ValueError:
        return fallback


def get_bool(name: str, default: Any = _UNSET) -> bool:
    v = var(name)
    raw = os.environ.get(name)
    if raw is None or raw == "":
        # empty == unset -> registered default, like every other
        # accessor (an empty export must not flip a default-True knob)
        return bool(v.default) if default is _UNSET else bool(default)
    return raw.lower() not in _FALSE_SPELLINGS


def describe() -> str:
    """Human-readable knob table (README / --help surface)."""
    rows = []
    for name in sorted(_REGISTRY):
        v = _REGISTRY[name]
        rows.append("%-32s %-5s default=%-12r %s%s"
                    % (v.name, v.kind, v.default, v.doc,
                       "  [import-time]" if v.import_time else ""))
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# The registry.  Grouped by owning module; the owning module still holds
# the semantics, this is the declaration + documentation site.
# ---------------------------------------------------------------------------

# engine.py — step-level bulk execution
register("MXNET_MODULE_BULK_SIZE", "int", None,
         "Opt Module.fit into K-step bulk dispatch (module/bulk.py); "
         "presence alone opts in, value is K.")

# parallel/buckets.py — bucketed gradient all-reduce
register("MXNET_KVSTORE_BUCKET_BYTES", "int", 4 * 1024 * 1024,
         "Gradient all-reduce bucket size cap; 0 forces the monolithic "
         "SPMD reduction.")
register("MXNET_KVSTORE_BUCKET_CHAIN", "bool", True,
         "Chain consecutive bucket reductions through "
         "optimization_barrier (stops the all-reduce combiner).")
register("MXNET_KVSTORE_BUCKET_IMPL", "str", "psum",
         "Bucket reduction implementation: 'psum' or 'ring' "
         "(manual ppermute reduce-scatter/all-gather).")

# autotune/ — self-tuning collectives (flight recorder -> bucket plan)
register("MXNET_AUTOTUNE_PLAN", "str", None,
         "Explicit tuned-plan JSON (python -m mxnet_tpu.autotune "
         "--tune ... --apply) applied to every bucketed gradient "
         "exchange in place of MXNET_KVSTORE_BUCKET_BYTES; an "
         "unreadable or invalid file raises (a typo'd plan silently "
         "falling back to the 4 MiB guess is a config bug).")
register("MXNET_AUTOTUNE_DIR", "str", None,
         "Directory of tuned-plan JSONs scanned at step build; a plan "
         "whose fingerprint (total gradient bytes + leaf count) "
         "matches the exchange being built supplies the bucket caps.  "
         "--apply writes here by default.")

# kvstore.py — gradient compression on the dist wire
register("MXNET_GRADIENT_COMPRESSION", "str", None,
         "Enable worker-side gradient compression on dist kvstores at "
         "create ('2bit' is the supported type): pushes travel as "
         "packed 2-bit codes with per-key error feedback, "
         "mxnet_kvstore_bytes_total{op=push} counts the compressed "
         "wire bytes.  Unset disables.")
register("MXNET_GRADIENT_COMPRESSION_THRESHOLD", "float", 0.5,
         "2-bit compression threshold: values >= t encode +t, <= -t "
         "encode -t, the rest 0 with the residual carried locally "
         "(ref: gradient_compression.h threshold param).")

# kvstore_server.py — parameter-server sync mode
register("MXNET_KVSTORE_SYNC_TIMEOUT", "float", 600.0,
         "Sync-pull progress deadline (seconds, resets on every applied "
         "round) before a stalled round aborts.")

# remat.py — mirror pass / rematerialization
register("MXNET_BACKWARD_DO_MIRROR", "bool", False,
         "Keep only conv/matmul residuals and rematerialize cheap "
         "activations in backward (jax.checkpoint mirror policy).")
register("MXNET_REMAT_POLICY", "str", "none",
         "Per-scope rematerialization policy (one string, shared "
         "registry across workload tiers): 'none'; transformer tier "
         "'block' (keep only block-boundary residuals) or 'attention' "
         "(recompute just the attention sub-graph); conv tier 'stage' "
         "(each resnet stage reruns in backward, only stage-boundary "
         "activations stay live) or 'conv_block' (each residual unit "
         "— finer boundaries, more kept, less recompute).")
register("MXNET_GRAD_ACCUM_STEPS", "int", 1,
         "Microbatch gradient accumulation inside the compiled step: "
         "the dispatch batch splits into this many microbatches, a "
         "lax.scan runs forward+backward per microbatch accumulating "
         "gradients (per-bucket flats on the bucketed/ZeRO-1 paths), "
         "and ONE bucketed reduce + fused update runs after the scan "
         "— effective batch = dispatch batch at one microbatch's "
         "activation memory.  1 disables (byte-identical step "
         "program).  Must divide the per-device batch.")

# transformer/ — decoder-only LM workload tier
register("MXNET_ATTENTION_IMPL", "str", "flash",
         "Transformer attention implementation: 'flash' (single-chip "
         "fused scan), 'ring' (KV rotation over the mesh's sp axis) "
         "or 'ulysses' (all-to-all head resharding over sp).")
register("MXNET_ZERO_STAGE", "int", 0,
         "Optimizer-state sharding: 0 replicates momenta on every dp "
         "rank (default); 1 = ZeRO-1 (each dp rank owns a 1/dp shard "
         "of every bucket's momenta; grads reduce-scatter, the update "
         "runs on the shard, params all-gather).")

# profiler.py — trace autostart (worker subprocess contract)
register("MXNET_PROFILER_AUTOSTART", "bool", False,
         "Start tracing at import and dump at exit (worker "
         "subprocesses).", import_time=True)
register("MXNET_PROFILER_FILENAME", "str", "profile.json",
         "Trace dump filename for the autostart path.",
         import_time=True)

# traceview/ — the ONE sanctioned XLA device-trace capture site
register("MXNET_TRACE_DIR", "str", None,
         "Arm the traceview device-timeline capture: the next steady-"
         "state training/serving dispatches are recorded through the "
         "one sanctioned jax.profiler wrapper and an attributed "
         "traceview_summary_rank{K}.json lands here.")
register("MXNET_TRACE_STEPS", "int", 3,
         "Dispatch windows to record once MXNET_TRACE_DIR is set "
         "(after one untraced warmup dispatch that absorbs compile).")

# dist.py / profiler rank contract — jax pod launch
register("MXNET_COORDINATOR_ADDRESS", "str", None,
         "host:port of process 0's coordination service; presence "
         "enables multi-process initialization.")
register("MXNET_NUM_PROCESSES", "int", 1,
         "Number of processes in the pod launch contract.")
register("MXNET_PROCESS_ID", "int", 0,
         "This process's rank in the pod launch contract.")

# _ps.py — parameter-server transport
register("MXNET_PS_SECRET", "str", None,
         "Shared HMAC secret authenticating PS messages.")
register("MXNET_PS_REQUEST_TIMEOUT", "float", 900.0,
         "Client-side PS request timeout (s); exceeds the server sync "
         "window so tolerated stragglers are not aborted client-side.")
register("MXNET_PS_HEARTBEAT_INTERVAL", "float", 5.0,
         "Worker->scheduler heartbeat period (s).  The heartbeat "
         "thread also queries dead peers each beat and feeds them to "
         "the flight-recorder header for merge_traces --health.")
register("MXNET_PS_RETRY_MAX", "int", 3,
         "Transport retries per PS request after a timeout/connection "
         "failure (reconnect + resend with exponential backoff); 0 "
         "fails fast like the pre-retry behavior.")
register("MXNET_PS_RETRY_BACKOFF_S", "float", 0.1,
         "Initial retry backoff (s); doubles per attempt with +-50% "
         "jitter so a rebooted server is not thundering-herded.")

# chaos.py — fault injection for the chaos harness
register("MXNET_CHAOS", "str", None,
         "Fault-injection spec: semicolon-separated rules "
         "'kind:k=v,k=v' with kinds drop_push / drop_sparse_pull / "
         "delay_collective / kill / nan_grad / slow_request / "
         "fail_execute / corrupt_shard / bad_version / slow_decode / "
         "kill_rank / cancel_request / stall_decode_tick "
         "(see mxnet_tpu/chaos.py).  Unset disables all injection.")

# module — non-finite gradient guard
register("MXNET_SKIP_NONFINITE_GRADS", "bool", False,
         "Check gradients for NaN/Inf before the kvstore push/update "
         "and skip the step (counting "
         "mxnet_training_skipped_steps_total) instead of poisoning "
         "the fleet.  Costs one host sync per step; off by default.")

# diagnostics.py — loss-spike divergence guard (the nonfinite guard's
# big sibling: a FINITE loss that exploded is garbage too)
register("MXNET_DIVERGENCE_WINDOW", "int", 0,
         "Loss-spike detector window (steps): once the window is "
         "full, a loss exceeding median + factor x |median| (or going "
         "non-finite) trips the divergence guard — under the elastic "
         "supervisor the run exits EXIT_DIVERGED=84 and is restored "
         "from the last VERIFIED checkpoint instead of training "
         "through garbage.  0 disables.")
register("MXNET_DIVERGENCE_FACTOR", "float", 3.0,
         "Divergence threshold: loss > window median + factor x "
         "|median| trips the guard (scale-relative above and below "
         "zero; see MXNET_DIVERGENCE_WINDOW).")

# sdc.py — silent-data-corruption defense (cross-rank fingerprint
# voting + supervisor quarantine + replay audit)
register("MXNET_SDC_CHECK_EVERY_N", "int", 0,
         "Cross-rank SDC fingerprint-vote cadence (steps): every N "
         "steps each rank fingerprints its post-update params per "
         "bucket (bit-exact wrapped uint32 word sum), the vectors are "
         "exchanged (PS rendezvous ops, or an in-graph all_gather on "
         "the shard_map tiers) and majority-voted; the minority rank "
         "dumps an 'sdc' flight event and exits EXIT_SDC=87 without "
         "saving, so the elastic supervisor QUARANTINES its slot and "
         "resumes survivors from the newest verified checkpoint.  0 "
         "(default) disables — the off path adds nothing to the "
         "compiled step or the fit loop.")
register("MXNET_SDC_EXCHANGE_TIMEOUT_S", "float", 60.0,
         "How long a PS-path SDC check waits for every rank's "
         "fingerprint report before declaring the round inconclusive "
         "and moving on (a vote must not take down a healthy fleet).")

# elastic/ — fleet supervisor (failure detection -> mesh reshape ->
# resume at the new world size)
register("MXNET_ELASTIC_MAX_RESTARTS", "int", 3,
         "Restart budget for the elastic supervisor: fleet relaunches "
         "allowed before it gives up and exits "
         "EXIT_RESTART_BUDGET=86.")
register("MXNET_ELASTIC_BACKOFF_S", "float", 1.0,
         "Initial supervisor restart backoff (s); doubles per "
         "consecutive restart with +-50% jitter (the _ps.py retry "
         "discipline applied to whole-fleet relaunches).")
register("MXNET_ELASTIC_REJOIN_S", "float", 0.0,
         "Bounded rejoin window (s): after a failure the supervisor "
         "waits this long for the failed slot's rejoin marker "
         "(slot{K}.rejoin in the supervisor state dir) before "
         "reshaping to W' = survivors; a slot that rejoins in time "
         "restores the full W.  0 reshapes immediately.")
register("MXNET_ELASTIC_GENERATION", "int", 0,
         "Fleet incarnation counter, exported by the supervisor to "
         "every child: stamped into flight-recorder headers and "
         "checkpoint sidecars/manifests so merge_traces --health "
         "attributes dumps to the right incarnation.")
register("MXNET_ELASTIC_SUPERVISED", "bool", False,
         "Set by the elastic supervisor on its children: failure "
         "paths that would otherwise need an operator (divergence "
         "guard) may exit with a restartable code instead.")
register("MXNET_ELASTIC_HEARTBEAT_DIR", "str", None,
         "Directory of per-rank heartbeat files (hb_rank{K}) the "
         "supervisor watches for hung-worker detection; set by the "
         "supervisor, touched by diagnostics.touch_heartbeat from the "
         "fit loops and the PS heartbeat thread.")
register("MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S", "float", 0.0,
         "A worker whose heartbeat file is staler than this is "
         "declared hung and SIGKILLed by the supervisor (restart "
         "follows the normal failure path).  0 disables hung "
         "detection (exit codes still supervise).")

# checkpoint.py — elastic checkpoint/resume (fault tolerance)
register("MXNET_CKPT_DIR", "str", None,
         "Default checkpoint directory for Module.fit when "
         "checkpoint_every_n is set without an explicit dir.")
register("MXNET_CKPT_EVERY_N", "int", 0,
         "Checkpoint every N optimizer steps in Module.fit; 0 disables "
         "(the checkpoint_every_n fit argument overrides).")
register("MXNET_CKPT_KEEP", "int", 3,
         "Completed checkpoint steps retained per directory; older "
         "steps are garbage-collected after each save. 0 keeps all.")
register("MXNET_CKPT_ASYNC", "bool", True,
         "Write checkpoint shards on a background thread so the host "
         "serialization overlaps the compiled step (the device->host "
         "snapshot itself is always synchronous).")
register("MXNET_CKPT_DRAIN_S", "float", 5.0,
         "How long the SIGTERM/watchdog preemption path waits for "
         "in-flight collectives to drain before checkpointing.")
register("MXNET_CKPT_VERIFY", "bool", True,
         "Verify shard sha256 digests against the per-step "
         "MANIFEST.json on load; a corrupt newest step falls back to "
         "the newest VERIFIED step (explicitly requested steps fail "
         "fast instead).  0 trusts disk blindly.")

# diagnostics.py — flight recorder / recompile tracking / metrics
register("MXNET_DUMP_DIR", "str", None,
         "Directory for relative-path telemetry artifacts "
         "(flightrecorder_rank*.json, profile_rank*.json, metrics "
         "expositions); unset writes to the CWD.  Explicit absolute "
         "paths always win.")
register("MXNET_FLIGHT_RECORDER_SIZE", "int", 256,
         "Collective flight-recorder ring capacity; 0 disables.")
register("MXNET_FLIGHT_RECORDER_FILE", "str", "flightrecorder.json",
         "Basename for flightrecorder_rank{K}.json dumps.")
register("MXNET_FLIGHT_RECORDER_DUMP", "str", None,
         "Dump the ring at exit: bool spellings honored, any other "
         "value is also the output path.")
register("MXNET_COLLECTIVE_TIMEOUT_S", "float", None,
         "Watchdog: collectives in flight longer than this are marked "
         "suspect and the ring dumps (run keeps going).")
register("MXNET_COLLECTIVE_ABORT_S", "float", None,
         "Watchdog escalation: a collective in flight longer than this "
         "checkpoints via the registered preemption hooks and aborts "
         "the process with exit code 85 (EXIT_WATCHDOG_ABORT) so a "
         "desynced fleet terminates restartably instead of hanging.")
register("MXNET_RECOMPILE_WARN_N", "int", 1,
         "Warn RECOMPILATION STORM when one step function compiles "
         "more than N times.")
register("MXNET_METRICS_FILE", "str", None,
         "Path for periodic Prometheus-text metric flushes.")
register("MXNET_METRICS_INTERVAL_S", "float", 30.0,
         "Period of the metrics file flush (s).")

# serving/ — batching model server (admission, deadlines, drain)
register("MXNET_SERVE_QUEUE_MAX", "int", 128,
         "Per-model admission bound (requests).  A submit arriving at a "
         "full queue is shed with reason=queue_full and a retry-after "
         "hint instead of growing an unbounded backlog.")
register("MXNET_SERVE_MAX_BATCH", "int", 32,
         "Largest dynamic batch (samples) the batcher assembles; also "
         "the top of the compiled batch-bucket ladder.")
register("MXNET_SERVE_BATCH_DEADLINE_MS", "float", 5.0,
         "How long the dynamic batcher holds the first queued request "
         "open for co-batching before dispatching a partial batch.")
register("MXNET_SERVE_DEADLINE_MS", "float", 1000.0,
         "Default per-request deadline; admitted requests that expire "
         "in the queue are dropped before dispatch (never batched), "
         "counted mxnet_serve_requests_total{outcome=expired}; a "
         "deadline already dead at submit sheds with reason=deadline.")
register("MXNET_SERVE_DRAIN_S", "float", 10.0,
         "Graceful drain budget: stop admitting, flush queued + "
         "in-flight batches, then exit (SIGTERM preemption-hook path).")
register("MXNET_SERVE_BREAKER_N", "int", 5,
         "Per-model circuit breaker: consecutive executor failures "
         "before the model fast-fails submits (reason=breaker_open) "
         "instead of queueing doomed work.  0 disables the breaker.")
register("MXNET_SERVE_BREAKER_RESET_S", "float", 5.0,
         "How long an open circuit breaker waits before letting one "
         "half-open probe batch through; success closes it.")
register("MXNET_SERVE_PORT", "int", 8000,
         "HTTP front-end port for python -m mxnet_tpu.serving --serve "
         "(predict + healthz/readyz/metrics).")
register("MXNET_SERVE_CANARY_PCT", "float", 25.0,
         "During ModelServer.reload, the percentage of dispatched "
         "batches routed to the NEW version while it is canaried; a "
         "failed canary batch is transparently re-executed on the "
         "stable version.  0 skips the canary and swaps as soon as "
         "the new version is compiled + warm.")
register("MXNET_SERVE_CANARY_MIN_N", "int", 20,
         "Canary batches observed before the promote-vs-rollback "
         "decision is made (too small and one unlucky batch decides; "
         "too large and a bad version canaries forever).")
register("MXNET_SERVE_ROLLBACK_ERR_RATIO", "float", 2.0,
         "Auto-rollback threshold: the canary rolls back when its "
         "error rate exceeds the stable version's error rate over the "
         "same window times this ratio (a canary that errors while "
         "stable is clean always rolls back).")

# serving/generate.py — autoregressive generation (paged KV cache +
# continuous batching)
register("MXNET_SERVE_KV_BLOCK_TOKENS", "int", 16,
         "Tokens per paged-KV-cache block.  Also the rounding unit of "
         "the prompt/cache bucket ladders, so every compiled shape is "
         "a whole number of blocks.")
register("MXNET_SERVE_GEN_SLOTS", "int", 8,
         "Concurrent sequences per generator (the continuous-batching "
         "slot count); also the top of the decode batch ladder.")
register("MXNET_SERVE_GEN_MAX_PROMPT", "int", 64,
         "Largest admissible prompt (tokens); the top of the compiled "
         "prefill prompt-length ladder (rounded up to a block).")
register("MXNET_SERVE_GEN_MAX_CONTEXT", "int", 256,
         "Largest prompt+output context (tokens); the top of the "
         "compiled decode cache-length ladder (rounded up to a "
         "block).")
register("MXNET_SERVE_GEN_MAX_NEW", "int", 32,
         "Default (and maximum) new tokens per generation request; "
         "submits asking for more shed with reason=too_large.")
register("MXNET_SERVE_GEN_BLOCKS", "int", 0,
         "KV-cache pool size in blocks (excluding the garbage block); "
         "0 sizes it so every slot can hold a full max-context "
         "sequence (no eviction pressure).")
register("MXNET_SERVE_GEN_PREFILL_BATCH", "int", 4,
         "Largest batched prefill (sequences admitted per tick); the "
         "top of the prefill batch ladder.  Bounds prefill's "
         "head-of-line blocking of in-flight decode ticks.")
register("MXNET_SERVE_REQTRACE_SIZE", "int", 256,
         "Request-trace recorder ring capacity (completed/rejected "
         "request records kept; serving/reqtrace.py).  0 disables "
         "recording entirely — the disabled path allocates nothing "
         "per token.")
register("MXNET_SERVE_REQTRACE_TOPK", "int", 8,
         "Slowest completed requests kept per sliding window for the "
         "tail-latency autopsy (reqtrace_rank{K}.json 'slowest' "
         "section + bench attribution shares).")
register("MXNET_SERVE_REQTRACE_WINDOW_S", "float", 60.0,
         "Sliding-window length (s) for the reqtrace top-K autopsy "
         "pool and the worst-sample latency/TPOT exemplars; also "
         "rate-limits the blown-deadline auto-dump to one per "
         "window.")

# image/image.py — decode pool
register("MXNET_CPU_WORKER_NTHREADS", "int", 1,
         "Decode worker threads for ImageIter augmentation.")

# io_pipeline.py — sharded multi-process decode pool + async device
# prefetch (the input-pipeline rearchitecture)
register("MXNET_IO_WORKERS", "int", 0,
         "Decode-pool worker processes for io_pipeline.InputPipeline; "
         "0 means cpu_count-1 (min 1).  Each worker owns a disjoint "
         "num_parts/part_index record slice.")
register("MXNET_IO_PREFETCH_DEPTH", "int", 2,
         "Device-prefetch depth: how many batches the async device "
         "stage keeps placed ahead of the consumer (2 = classic "
         "double buffering: batch k+1 transfers while k computes).")
register("MXNET_IO_POOL_SLOTS", "int", 4,
         "Shared-memory batch slots per decode worker; bounds how far "
         "a worker can run ahead of the consumer (backpressure).")
register("MXNET_IO_START_METHOD", "str", None,
         "Decode-pool start method: 'fork' or 'spawn'.  Unset picks "
         "fork when the backing iterator supports the jax-free "
         "next_raw contract (workers never touch jax, so forking a "
         "jax-initialized parent is safe), spawn otherwise.")

