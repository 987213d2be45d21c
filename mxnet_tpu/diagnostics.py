"""mx.diagnostics — runtime health: flight recorder, recompile
tracking, step-metrics registry.

The profiler (profiler.py) records what happened on one healthy rank;
this module records enough to explain a hung, desynced or slow FLEET —
the gap NCCL/PyTorch-style flight recorders and MLPerf structured run
logs close.  Three cooperating pieces:

  * **Collective flight recorder** — a lock-protected ring buffer
    (``MXNET_FLIGHT_RECORDER_SIZE``, default 256; 0 disables) holding
    the last N collectives this process issued: kvstore push/pull/
    allreduce and every per-bucket reduction dispatched by
    ``FusedTrainStep``/``KVStoreTPU``.  Each entry carries a
    monotonically increasing collective seq number, op, bucket id,
    keys, payload bytes, dtype, rank, enqueue/complete wall-clock
    timestamps and a completion state.  Dumped to
    ``flightrecorder_rank{K}.json`` on demand (:func:`dump`), at
    interpreter exit (via profiler.py's shared shutdown path — always
    when ``MXNET_FLIGHT_RECORDER_DUMP`` is set, and unconditionally
    when any entry is still in flight, i.e. the rank died mid-
    collective), and on SIGTERM/SIGUSR1.  A watchdog
    (``MXNET_COLLECTIVE_TIMEOUT_S``) marks entries in flight longer
    than the timeout as ``suspect`` and dumps WITHOUT killing the run.
    ``tools/merge_traces.py --health`` ingests the per-rank dumps and
    names the rank + seq/bucket/key a desynced fleet diverged at.

  * **Recompile tracking** — :func:`instrument_jit` wraps the compiled
    step callables (FusedTrainStep's jits, Module.fit's bulk scan) and
    counts/times every XLA compilation they trigger (via the jitted
    function's ``_cache_size`` when the toolchain exposes it, aval-
    signature tracking otherwise), stamps ``compile`` spans into the
    trace, and — because a silent recompilation storm (shape/dtype
    churn) can double step time with no error anywhere — emits one loud
    warning per step function when it compiles more than
    ``MXNET_RECOMPILE_WARN_N`` (default 1) times, with the offending
    avals in the message.  :func:`recompile_stats` is the queryable
    surface.

  * **Step-metrics registry** — a small gauge/counter/histogram
    time-series registry (:data:`metrics`) fed by ``fit()`` and
    ``Speedometer``: step_time, samples/s, loss, allocator peak,
    recompiles, kvstore/io bytes.  ``dump_json()`` for a harness,
    ``to_prom()`` Prometheus text exposition for external scrapers,
    ``MXNET_METRICS_FILE`` (+ ``MXNET_METRICS_INTERVAL_S``) for a
    periodically flushed exposition file.

``python -m mxnet_tpu.diagnostics --self-test`` exercises ring-buffer
wraparound, the signal-handler dump and prom-text rendering (tier-1 CI
via tests/test_diagnostics.py).
"""
from __future__ import annotations

import json
import logging
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "FlightRecorder", "recorder", "record_collective", "record_start",
    "record_complete", "set_bucket_plan", "bucket_plan", "dump",
    "flight_enabled", "instrument_jit", "recompile_stats",
    "reset_recompile_stats", "recorded_steps", "Gauge", "Counter",
    "Histogram", "MetricsRegistry", "metrics", "record_step",
    "validate_prom_text", "EXIT_PREEMPTED", "EXIT_WATCHDOG_ABORT",
    "EXIT_DIVERGED",
    "register_preemption_hook", "unregister_preemption_hook",
    "run_preemption_hooks", "register_dump_hook",
    "unregister_dump_hook", "run_dump_hooks",
    "set_dead_peers", "dead_peers",
    "generation", "touch_heartbeat", "DivergenceError",
    "DivergenceGuard", "loss_signal",
]

_log = logging.getLogger(__name__)

DEFAULT_RING_SIZE = 256

#: SIGTERM landed, in-flight collectives drained, preemption hooks
#: (checkpoint) ran — the run is resumable from its checkpoint dir.
EXIT_PREEMPTED = 83
#: the divergence guard (MXNET_DIVERGENCE_WINDOW) tripped under the
#: elastic supervisor: the loss spiked past the windowed threshold (or
#: went non-finite), evidence dumped, process exited WITHOUT saving the
#: poisoned state so the supervisor restores the last VERIFIED
#: checkpoint.
EXIT_DIVERGED = 84
#: the collective watchdog's second threshold (MXNET_COLLECTIVE_ABORT_S)
#: fired: the fleet was permanently desynced, evidence dumped,
#: checkpoint attempted, process aborted restartably instead of hanging.
EXIT_WATCHDOG_ABORT = 85


def _dump_dir_path(path: str) -> str:
    """Relative artifact paths land under MXNET_DUMP_DIR (created on
    demand) so test/bench runs stop littering the CWD; absolute paths —
    and unset env — pass through untouched."""
    if os.path.isabs(path):
        return path
    from . import env as _envmod

    base = _envmod.get_str("MXNET_DUMP_DIR")
    if not base:
        return path
    try:
        os.makedirs(base, exist_ok=True)
    except OSError:
        return path
    return os.path.join(base, path)


# ---------------------------------------------------------------------------
# preemption hooks: the bridge from "evidence dumped" to "run recovers".
# Module.fit registers a checkpoint closure here; the SIGTERM handler and
# the watchdog's abort threshold invoke them (dump -> drain -> hooks ->
# exit) so a preempted or permanently-desynced fleet terminates
# RESTARTABLY instead of dying stateless or hanging forever.
# ---------------------------------------------------------------------------
# reentrant: SIGTERM may land on the main thread WHILE it is inside
# register/unregister holding this lock — run_preemption_hooks must
# still be able to take it (the same self-deadlock class the flight
# recorder's ring lock was converted to RLock for)
_preempt_lock = threading.RLock()
_preempt_hooks: "Dict[Any, Any]" = {}
_dead_peers_lock = threading.Lock()
_dead_peers: List[str] = []


def register_preemption_hook(fn, key: Any = None) -> Any:
    """Register ``fn()`` to run when this process is preempted (SIGTERM)
    or watchdog-aborted.  Hooks must be best-effort-safe: they run in a
    signal handler / watchdog thread.  Returns the key for
    :func:`unregister_preemption_hook`.

    Also arms the SIGTERM handler immediately: normally it installs on
    the first recorded collective, but a preemption landing during the
    long FIRST compile (no collective yet) must still checkpoint-and-
    exit-83 rather than die bare."""
    key = key if key is not None else id(fn)
    with _preempt_lock:
        _preempt_hooks[key] = fn
    if not recorder._signals_installed:
        recorder.install_signal_handlers()
    return key


def unregister_preemption_hook(key: Any) -> None:
    with _preempt_lock:
        _preempt_hooks.pop(key, None)


def run_preemption_hooks(reason: str) -> int:
    """Run every registered hook (newest first); returns how many ran
    without raising.  Never raises — this is the last thing a dying
    process does."""
    with _preempt_lock:
        hooks = list(_preempt_hooks.items())
    ran = 0
    for key, fn in reversed(hooks):
        try:
            fn()
            ran += 1
        except Exception:
            _log.exception("preemption hook %r failed (%s)", key, reason)
    return ran


_dump_hooks_lock = threading.RLock()
_dump_hooks: "Dict[Any, Any]" = {}


def register_dump_hook(fn, key: Any = None) -> Any:
    """Register ``fn(reason)`` to run whenever this process dumps
    evidence on a signal (SIGUSR1/SIGTERM) — the way the serving
    request recorder rides the flight recorder's shutdown path.
    Unlike preemption hooks, dump hooks have NO exit semantics: they
    only persist artifacts.  Also arms the signal handlers, same as
    :func:`register_preemption_hook`."""
    key = key if key is not None else id(fn)
    with _dump_hooks_lock:
        _dump_hooks[key] = fn
    if not recorder._signals_installed:
        recorder.install_signal_handlers()
    return key


def unregister_dump_hook(key: Any) -> None:
    with _dump_hooks_lock:
        _dump_hooks.pop(key, None)


def run_dump_hooks(reason: str) -> int:
    """Run every registered dump hook; returns how many ran without
    raising.  Never raises — this runs inside signal handlers."""
    with _dump_hooks_lock:
        hooks = list(_dump_hooks.items())
    ran = 0
    for key, fn in hooks:
        try:
            fn(reason)
            ran += 1
        except Exception:
            _log.exception("dump hook %r failed (%s)", key, reason)
    return ran


def set_dead_peers(peers) -> None:
    """Record heartbeat-declared dead peers (_ps.Heartbeat feeds this
    from the scheduler's dead_nodes query) — stamped into every flight
    dump header so ``merge_traces.py --health`` can name them."""
    with _dead_peers_lock:
        _dead_peers[:] = [str(p) for p in (peers or [])]


def dead_peers() -> List[str]:
    with _dead_peers_lock:
        return list(_dead_peers)


def generation() -> int:
    """This process's fleet incarnation (``MXNET_ELASTIC_GENERATION``,
    exported by the elastic supervisor; 0 for unsupervised runs) —
    stamped into flight-dump headers so post-mortem tooling attributes
    artifacts to the right incarnation.  One reader for the contract:
    ``dist.generation``."""
    from . import dist as _dist

    return _dist.generation()


_hb_lock = threading.Lock()
_hb_last = 0.0
_hb_path: Optional[str] = None


def touch_heartbeat(min_interval_s: float = 0.5) -> Optional[str]:
    """Liveness beacon for the elastic supervisor: utime/create
    ``$MXNET_ELASTIC_HEARTBEAT_DIR/hb_rank{K}``.  Called from the fit
    loops (per step) and the PS heartbeat thread; rate-limited so a
    fast step loop pays one ``utime`` every ``min_interval_s`` at most.
    No-op (None) when the env is unset — unsupervised runs pay one env
    lookup."""
    global _hb_last, _hb_path
    from . import env as _envmod

    d = _envmod.get_str("MXNET_ELASTIC_HEARTBEAT_DIR")
    if not d:
        return None
    now = time.monotonic()
    with _hb_lock:
        if now - _hb_last < min_interval_s and _hb_path:
            return _hb_path
        _hb_last = now
    path = os.path.join(d, "hb_rank%d" % _rank_info()[0])
    try:
        os.makedirs(d, exist_ok=True)
        if os.path.exists(path):
            os.utime(path)
        else:
            with open(path, "w"):
                pass
        _hb_path = path
        return path
    except OSError:
        return None


def loss_signal(name_values) -> Optional[float]:
    """The loss-like scalar among a metric's ``(name, value)`` pairs —
    what the conv-path divergence guard feeds on: the first metric
    whose name says loss/entropy/perplexity (spiking accuracy is not
    divergence); failing that, any NON-FINITE metric value (garbage is
    garbage whatever the metric is called)."""
    import math

    fallback = None
    for name, value in (name_values or ()):
        try:
            v = float(value)
        except (TypeError, ValueError):
            continue
        n = str(name).lower()
        if any(t in n for t in ("loss", "entropy", "perplex", "nll")):
            return v
        if not math.isfinite(v) and fallback is None:
            fallback = v
    return fallback


class DivergenceError(RuntimeError):
    """The loss-spike guard tripped outside supervision: training was
    stopped rather than continued through garbage.  Under the elastic
    supervisor the process exits ``EXIT_DIVERGED`` instead so the fleet
    is restored from the last verified checkpoint automatically."""


class DivergenceGuard:
    """Loss-spike detector (``MXNET_DIVERGENCE_WINDOW`` /
    ``MXNET_DIVERGENCE_FACTOR``) — the ``MXNET_SKIP_NONFINITE_GRADS``
    idea extended from "the gradients are NaN" to "the loss exploded":
    once ``window`` losses are observed, a step whose loss exceeds
    ``median + factor x |median|`` of the window (or is non-finite)
    is divergence.

    :meth:`check` feeds one loss and returns True on a trip (counted in
    ``mxnet_training_divergence_trips_total``).  :meth:`trip` applies
    the policy: under the elastic supervisor
    (``MXNET_ELASTIC_SUPERVISED``) dump the flight ring and exit
    ``EXIT_DIVERGED=84`` WITHOUT checkpointing the poisoned state —
    the supervisor then restores the last verified checkpoint;
    unsupervised, raise :class:`DivergenceError`."""

    def __init__(self, window: Optional[int] = None,
                 factor: Optional[float] = None):
        from . import env as _envmod

        self.window = int(_envmod.get_int("MXNET_DIVERGENCE_WINDOW")
                          if window is None else window)
        self.factor = float(_envmod.get_float("MXNET_DIVERGENCE_FACTOR")
                            if factor is None else factor)
        self._history: List[float] = []

    @property
    def enabled(self) -> bool:
        return self.window > 0

    def check(self, loss: float, step: Optional[int] = None) -> bool:
        """Feed one step's loss; True when it diverged from the window.
        The spiking loss is NOT folded into the baseline (one bad step
        must not drag the median up toward itself)."""
        if not self.enabled:
            return False
        import math

        loss = float(loss)
        finite = math.isfinite(loss)
        spiked = not finite
        if finite and len(self._history) >= self.window:
            med = sorted(self._history)[len(self._history) // 2]
            # threshold = median + factor x |median|: scale-relative
            # above AND below zero (losses can be legitimately
            # negative — a continuous-density NLL — and a zero/negative
            # median must not make every positive step a "spike")
            spiked = loss > med + self.factor * max(abs(med), 1e-8)
        if spiked:
            metrics.counter(
                "mxnet_training_divergence_trips_total",
                help="steps the loss-spike divergence guard flagged"
            ).inc()
            _log.error(
                "DIVERGENCE: loss %r at step %s tripped the guard "
                "(window %d, factor %.2f, window median %s)",
                loss, step, self.window, self.factor,
                sorted(self._history)[len(self._history) // 2]
                if self._history else None)
            return True
        if finite:
            self._history.append(loss)
            if len(self._history) > self.window:
                del self._history[0]
        return False

    def trip(self, step: Optional[int] = None) -> None:
        """Apply the divergence policy (see class docstring)."""
        from . import env as _envmod

        if recorder.n_recorded():
            # empty rings never dump (the artifact-hygiene contract:
            # a collective-less process must not litter evidence files)
            recorder.dump(reason="divergence")
        if _envmod.get_bool("MXNET_ELASTIC_SUPERVISED"):
            _log.error(
                "divergence at step %s under the elastic supervisor: "
                "exiting %d so the fleet restores the last VERIFIED "
                "checkpoint (this state is deliberately NOT saved)",
                step, EXIT_DIVERGED)
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(EXIT_DIVERGED)
        raise DivergenceError(
            "loss diverged at step %s (window %d, factor %.2f); "
            "restore from the last verified checkpoint — under "
            "python -m mxnet_tpu.elastic this restore is automatic"
            % (step, self.window, self.factor))


def _dump_env() -> Tuple[bool, Optional[str]]:
    """ONE parse of ``MXNET_FLIGHT_RECORDER_DUMP`` shared by the atexit
    leg and ``dump_path`` so they can never disagree: returns
    ``(dump_wanted, path_override)``.  Boolean spellings (any case) are
    honored both ways — 1/true/yes/on request a dump at the configured
    path, 0/false/no/off (and unset/empty) disable it; any other value
    both requests the dump AND carries the output path."""
    from . import env as _envmod

    raw = _envmod.get_raw("MXNET_FLIGHT_RECORDER_DUMP")
    if raw in (None, "") or raw.lower() in ("0", "false", "no", "off"):
        return False, None
    if raw.lower() in ("1", "true", "yes", "on"):
        return True, None
    return True, raw


def _rank_info() -> Tuple[int, int]:
    """(rank, num_workers) — same precedence as the profiler's trace
    dumps (explicit set_rank, then launcher env), so the two artifact
    families always agree on who rank K is."""
    from . import profiler as _profiler

    return _profiler._dist_info()


# ---------------------------------------------------------------------------
# collective flight recorder
# ---------------------------------------------------------------------------
class FlightRecorder:
    """Ring buffer of the last N collectives issued by this process.

    States: ``in_flight`` (enqueued, not yet returned), ``completed``,
    ``error`` (the collective raised), ``suspect`` (in flight longer
    than ``MXNET_COLLECTIVE_TIMEOUT_S`` — stamped by the watchdog).
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from . import env as _envmod

            capacity = _envmod.get_int("MXNET_FLIGHT_RECORDER_SIZE",
                                       DEFAULT_RING_SIZE)
        self.capacity = max(int(capacity), 0)
        # reentrant: the SIGTERM/SIGUSR1 handlers dump from the main
        # thread, which may already hold the lock inside start()
        self._lock = threading.RLock()
        self._entries: List[dict] = []   # ring, oldest first
        self._seq = 0
        self._dropped = 0                # entries overwritten by the ring
        self._open: Dict[int, dict] = {}  # seq -> in-flight entry
        self._bucket_plan: Optional[dict] = None
        self._bucket_plan_owner: Optional[int] = None
        self._signals_installed = False
        self._watchdog: Optional[threading.Thread] = None
        self._suspect_dumped: set = set()  # seqs already dump-reported

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    # -- recording -----------------------------------------------------
    def start(self, op: str, keys=None, bucket: Optional[int] = None,
              nbytes: int = 0, dtype=None, args: Optional[dict] = None
              ) -> Optional[int]:
        """Record the enqueue of one collective; returns its seq (None
        when disabled).  Never raises — a diagnostic must not fail the
        collective it is recording."""
        if not self.enabled:
            return None
        try:
            from . import chaos as _chaos

            fired = None
            if _chaos.enabled():
                # chaos 'delay_collective': a seeded straggler — the
                # sleep happens where the collective is issued, so the
                # watchdog/straggler analyses see a real stall
                fired = _chaos.maybe_delay(str(op))
            entry = {
                "seq": -1, "op": str(op),
                "keys": self._norm_keys(keys),
                "bucket": None if bucket is None else int(bucket),
                "bytes": int(nbytes), "dtype": None if dtype is None
                else str(dtype),
                "enqueue_ts": time.time(), "complete_ts": None,
                "state": "in_flight",
            }
            if fired:
                # seeded stall: --health/traceview must report it as
                # "INJECTED STALL (chaos)", never as an organic straggler
                entry["injected"] = True
                entry["injected_kind"] = fired.get("kind")
            if args:
                entry["args"] = dict(args)
            with self._lock:
                entry["seq"] = self._seq
                self._seq += 1
                self._entries.append(entry)
                if len(self._entries) > self.capacity:
                    evicted = self._entries.pop(0)
                    self._dropped += 1
                    self._open.pop(evicted["seq"], None)
                self._open[entry["seq"]] = entry
            self._arm()
            return entry["seq"]
        except Exception:
            return None

    def complete(self, seq: Optional[int], state: str = "completed"
                 ) -> None:
        if seq is None:
            return
        try:
            with self._lock:
                entry = self._open.pop(seq, None)
                if entry is not None:
                    entry["complete_ts"] = time.time()
                    entry["state"] = state
        except Exception:
            pass

    @staticmethod
    def _norm_keys(keys) -> Optional[list]:
        if keys is None:
            return None
        if isinstance(keys, (list, tuple)):
            return [str(k) for k in keys]
        return [str(keys)]

    # -- state ---------------------------------------------------------
    def set_bucket_plan(self, plan_meta: Optional[dict],
                        owner: Optional[int] = None) -> None:
        """Stamp (or clear) the header's bucket plan.  An owned clear
        (``plan_meta=None`` with an ``owner`` token) only takes effect
        when that same owner stamped the current plan: a non-bucketed
        step building next to a still-live bucketed one must not erase
        the plan the live step's bucket_reduce entries run under.  An
        unowned clear is unconditional."""
        with self._lock:
            if plan_meta is None and owner is not None and \
                    self._bucket_plan_owner != owner:
                return
            self._bucket_plan = dict(plan_meta) if plan_meta else None
            self._bucket_plan_owner = owner if plan_meta else None

    def bucket_plan(self) -> Optional[dict]:
        with self._lock:
            return dict(self._bucket_plan) if self._bucket_plan else None

    def n_recorded(self) -> int:
        """Total collectives ever recorded (ring evictions included)."""
        with self._lock:
            return self._seq

    def last_completed_seq(self) -> int:
        """Highest seq with state completed (-1 if none)."""
        with self._lock:
            done = [e["seq"] for e in self._entries
                    if e["state"] == "completed"]
        return max(done) if done else -1

    def in_flight(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._entries
                    if e["state"] in ("in_flight", "suspect")]

    def snapshot(self) -> Tuple[dict, List[dict]]:
        """(header, entries) under one lock acquisition."""
        rank, num_workers = _rank_info()
        with self._lock:
            header = {
                "flight_recorder": True,
                "rank": rank, "num_workers": num_workers,
                "capacity": self.capacity, "next_seq": self._seq,
                "dropped": self._dropped,
                "bucket_plan": dict(self._bucket_plan)
                if self._bucket_plan else None,
                "dead_peers": dead_peers(),
                "generation": generation(),
                "pid": os.getpid(), "dump_ts": time.time(),
            }
            entries = [dict(e) for e in self._entries]
        return header, entries

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._open.clear()
            self._seq = 0
            self._dropped = 0
            self._suspect_dumped.clear()

    # -- dumps ---------------------------------------------------------
    def dump_path(self, base: Optional[str] = None) -> str:
        """``flightrecorder_rank{K}.json`` — the rank suffix is always
        present (rank 0 of 1 included) so ``--health`` can glob one
        pattern on any fleet size."""
        if base is None:
            from . import env as _envmod

            base = _envmod.get_str("MXNET_FLIGHT_RECORDER_FILE")
            _, path_override = _dump_env()
            if path_override:
                base = path_override  # the dump flag may carry the path
        rank, _ = _rank_info()
        root, ext = os.path.splitext(base)
        return _dump_dir_path("%s_rank%d%s" % (root, rank, ext or ".json"))

    def dump(self, path: Optional[str] = None, reason: str = "on_demand"
             ) -> Optional[str]:
        """Persist the ring to JSON; returns the path (None when
        disabled).  Safe to call from signal handlers and atexit."""
        if not self.enabled:
            return None
        try:
            header, entries = self.snapshot()
            header["reason"] = reason
            fname = path if path is not None else self.dump_path()
            with open(fname, "w") as f:
                json.dump({"header": header, "entries": entries}, f)
            return fname
        except Exception:
            return None

    # -- signal handlers + watchdog -------------------------------------
    def _arm(self) -> None:
        """First-record arming: signal handlers (main thread only) and
        the collective watchdog (when the suspect-timeout or the abort
        escalation env is set)."""
        if not self._signals_installed:
            self.install_signal_handlers()
        from . import env as _envmod

        timeout = _envmod.get_float("MXNET_COLLECTIVE_TIMEOUT_S", None)
        abort = _envmod.get_float("MXNET_COLLECTIVE_ABORT_S", None)
        if (timeout or abort) and self._watchdog is None:
            self._start_watchdog(timeout, abort)

    def drain(self, timeout_s: float) -> bool:
        """Wait for in-flight collectives to complete (suspects never
        will — they don't block the drain past the timeout).  The
        SIGTERM/abort path calls this BEFORE checkpointing so the
        snapshot isn't taken mid-collective.  Returns True when nothing
        is left in flight."""
        deadline = time.monotonic() + max(timeout_s, 0.0)
        while time.monotonic() < deadline:
            pending = [e for e in self.in_flight()
                       if e["state"] == "in_flight"]
            if not pending:
                break
            time.sleep(0.01)
        return not self.in_flight()

    def install_signal_handlers(self) -> bool:
        """SIGUSR1 dumps without disturbing the run, then chains to any
        handler the app installed (the default action — terminate — is
        NOT chained).

        SIGTERM is the preemption path, with an EXPLICIT ordering
        contract (covered by a subprocess test so it can't silently
        regress):

          1. **dump** the flight ring (reason=SIGTERM) — evidence
             first: a hook that hangs must not cost the post-mortem;
          2. **drain** in-flight collectives (MXNET_CKPT_DRAIN_S) so
             the checkpoint isn't taken mid-collective;
          3. **checkpoint** via the registered preemption hooks
             (Module.fit registers one while fitting);
          4. **exit(EXIT_PREEMPTED=83)** when a hook ran — the run is
             resumable, and the launcher can tell a clean preemption
             from a crash; otherwise **chain** to the previous handler
             (default: die) so external timeouts still kill the
             process AND leave the artifact behind."""
        if threading.current_thread() is not threading.main_thread():
            # don't burn the one-shot flag: a later main-thread
            # collective must still get to install the handlers
            return False
        self._signals_installed = True  # one attempt per recorder
        try:
            prev_usr1 = signal.getsignal(signal.SIGUSR1)

            def _usr1(signum, frame):
                self.dump(reason="SIGUSR1")
                run_dump_hooks("SIGUSR1")
                # SIG_DFL/SIG_IGN are not callable: only a handler the
                # app actually installed runs after the dump
                if callable(prev_usr1):
                    prev_usr1(signum, frame)

            prev_term = signal.getsignal(signal.SIGTERM)

            def _term(signum, frame):
                # n_recorded guard (same contract as the atexit leg): a
                # process that never issued a collective — a serving
                # demo, the PS scheduler — has no evidence to dump, and
                # an empty-ring dump would litter the CWD (or clobber a
                # worker's real dump) with a useless artifact
                if self.n_recorded():
                    self.dump(reason="SIGTERM")                # 1. dump
                run_dump_hooks("SIGTERM")  # serving autopsy et al.
                from . import env as _envmod

                try:
                    drain_s = _envmod.get_float("MXNET_CKPT_DRAIN_S")
                except Exception:
                    drain_s = 5.0
                self.drain(drain_s)                            # 2. drain
                ran = run_preemption_hooks("SIGTERM")     # 3. checkpoint
                if ran:
                    _log.warning(
                        "SIGTERM: flight ring dumped, collectives "
                        "drained, %d preemption hook(s) checkpointed — "
                        "exiting %d (resumable)", ran, EXIT_PREEMPTED)
                    os._exit(EXIT_PREEMPTED)              # 4. exit 83
                if prev_term is signal.SIG_IGN:
                    return  # the app deliberately ignores SIGTERM
                if callable(prev_term):                   # 4'. chain
                    prev_term(signum, frame)
                else:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGUSR1, _usr1)
            signal.signal(signal.SIGTERM, _term)
            return True
        except (ValueError, OSError, AttributeError):
            # non-main thread / restricted host / platform without the
            # signals: recording still works, on-signal dumps don't
            return False

    def _start_watchdog(self, timeout_s: Optional[float],
                        abort_s: Optional[float] = None) -> None:
        def loop():
            base = min(t for t in (timeout_s, abort_s) if t)
            period = max(min(base / 4.0, 5.0), 0.05)
            while True:
                time.sleep(period)
                try:
                    self.check_timeouts(timeout_s, abort_s=abort_s)
                except Exception:
                    pass

        t = threading.Thread(target=loop, name="mx-collective-watchdog",
                             daemon=True)
        self._watchdog = t
        t.start()

    def check_timeouts(self, timeout_s: Optional[float],
                       abort_s: Optional[float] = None) -> int:
        """Two-threshold watchdog (the watchdog thread calls this on its
        period; tests call it directly).  Returns the suspect count.

        * past ``timeout_s``: mark in-flight entries suspect + dump when
          NEW suspects appeared — diagnosis, the run keeps going;
        * past ``abort_s`` (MXNET_COLLECTIVE_ABORT_S): escalate — the
          collective is never completing (permanent desync / dead
          peer), so dump, checkpoint via the preemption hooks, and
          abort with EXIT_WATCHDOG_ABORT so the fleet terminates
          RESTARTABLY instead of hanging forever."""
        now = time.time()
        n_suspect = 0
        oldest_age = 0.0
        with self._lock:
            suspects = set()
            for e in self._entries:
                age = now - e["enqueue_ts"]
                if e["state"] == "in_flight" and \
                        timeout_s is not None and age > timeout_s:
                    e["state"] = "suspect"
                if e["state"] in ("in_flight", "suspect"):
                    oldest_age = max(oldest_age, age)
                if e["state"] == "suspect":
                    n_suspect += 1
                    suspects.add(e["seq"])
            # per-seq tracking, NOT a high-water count: a later hang
            # with fewer simultaneous suspects than an earlier,
            # recovered incident must still dump
            newly = bool(suspects - self._suspect_dumped)
            self._suspect_dumped |= suspects
        if abort_s is not None and oldest_age > abort_s:
            self._escalate_abort(oldest_age, abort_s)
        if newly:
            _log.warning(
                "collective watchdog: %d collective(s) in flight longer "
                "than %.1fs — dumping flight recorder to %s (the run is "
                "NOT killed)", n_suspect, timeout_s, self.dump_path())
            self.dump(reason="watchdog_timeout")
        return n_suspect

    def _escalate_abort(self, age_s: float, abort_s: float) -> None:
        """The escalation leg: same explicit ordering as SIGTERM (dump
        -> drain is pointless here, the collective IS the hang ->
        checkpoint hooks -> abort with the documented exit code)."""
        _log.error(
            "collective watchdog ESCALATION: a collective has been in "
            "flight %.1fs (> MXNET_COLLECTIVE_ABORT_S=%.1fs) — the "
            "fleet is permanently desynced.  Dumping evidence, "
            "checkpointing if possible, aborting with exit code %d so "
            "the run can be restarted from its last checkpoint.",
            age_s, abort_s, EXIT_WATCHDOG_ABORT)
        self.dump(reason="watchdog_abort")
        ran = run_preemption_hooks("watchdog_abort")
        if ran:
            _log.error("watchdog abort: %d preemption hook(s) "
                       "checkpointed before exit", ran)
        # os._exit, not sys.exit: this may run on the watchdog thread,
        # and the main thread is wedged inside the hung collective
        os._exit(EXIT_WATCHDOG_ABORT)


#: process-wide recorder (capacity from MXNET_FLIGHT_RECORDER_SIZE)
recorder = FlightRecorder()


def flight_enabled() -> bool:
    return recorder.enabled


def record_start(op: str, **kw) -> Optional[int]:
    return recorder.start(op, **kw)


def record_complete(seq: Optional[int], state: str = "completed") -> None:
    recorder.complete(seq, state)


class record_collective:
    """Context manager recording one collective: entry at enter,
    completion (or ``error``) at exit.  No-op when disabled."""

    def __init__(self, op: str, keys=None, bucket: Optional[int] = None,
                 nbytes: int = 0, dtype=None, args: Optional[dict] = None):
        self._kw = dict(keys=keys, bucket=bucket, nbytes=nbytes,
                        dtype=dtype, args=args)
        self._op = op
        self.seq: Optional[int] = None

    def __enter__(self):
        self.seq = recorder.start(self._op, **self._kw)
        return self

    def __exit__(self, exc_type, exc, tb):
        recorder.complete(self.seq,
                          "completed" if exc_type is None else "error")
        return False


def set_bucket_plan(plan_meta: Optional[dict],
                    owner: Optional[int] = None) -> None:
    """Stamp the bucket plan (count/bytes/cap — buckets.plan_meta) into
    the flight-recorder header so every dump is self-describing about
    which reduction schedule produced it.  Step builders pass their
    ``id()`` as ``owner`` so a monolithic rebuild only clears its OWN
    stale plan, never one a different live bucketed step stamped."""
    recorder.set_bucket_plan(plan_meta, owner=owner)


def bucket_plan() -> Optional[dict]:
    return recorder.bucket_plan()


def dump(path: Optional[str] = None) -> Optional[str]:
    """On-demand flight-recorder dump -> flightrecorder_rank{K}.json."""
    return recorder.dump(path=path, reason="on_demand")


def _atexit_dump() -> None:
    """The flight-recorder leg of profiler.py's shared shutdown path:
    dump when explicitly requested (MXNET_FLIGHT_RECORDER_DUMP) or when
    any collective never completed (the rank died mid-run — exactly the
    evidence --health needs); always flush the metrics file if one is
    configured."""
    try:
        want, _ = _dump_env()
        # n_recorded guard: a process that never issued a collective
        # (the PS scheduler/server, which inherits the launcher env and
        # may share rank 0's dump name) must not overwrite a worker's
        # evidence with an empty ring
        if recorder.enabled and recorder.n_recorded() and \
                (want or recorder.in_flight()):
            recorder.dump(reason="atexit")
    except Exception:
        pass
    try:
        metrics.flush()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# recompile tracking
# ---------------------------------------------------------------------------
_recompile_lock = threading.RLock()
_recompile: Dict[str, dict] = {}
_recompile_warned: Dict[str, bool] = {}
# name -> (wrapped jitted fn, last-compiled call's abstract arg specs,
# step meta like compute_dtype): the static-analysis auditor
# (mxnet_tpu/analysis) re-lowers each recorded step from these specs to
# audit its jaxpr offline — captured only when a call actually
# compiled, so the hot path pays nothing
_recorded_steps: Dict[str, Tuple[Any, tuple, dict]] = {}


def _arg_specs(args) -> tuple:
    """Args with every array leaf replaced by its ShapeDtypeStruct —
    enough to re-``lower`` the jitted function without holding (or
    donating) live buffers.  A device array's spec carries its
    sharding: ``lower`` then finds the lowering and the executable the
    call itself made (jax caches both by it) and costs no second
    compile, which is what ``traceview.program_scopes`` stands on."""
    import jax

    def spec(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(
                tuple(shape), dtype,
                sharding=getattr(x, "sharding", None))
        return x

    return jax.tree_util.tree_map(spec, args)


def recorded_steps() -> Dict[str, Tuple[Any, tuple, dict]]:
    """{name: (jitted fn, arg specs, meta)} for every instrumented
    compiled path that has compiled at least once in this process —
    the auditor's work list."""
    with _recompile_lock:
        return dict(_recorded_steps)


def _warn_threshold() -> int:
    from . import env as _envmod

    return _envmod.get_int("MXNET_RECOMPILE_WARN_N", 1)


def _avals_of(args) -> tuple:
    """Hashable (shape, dtype) signature of a call's array arguments —
    the churn axis recompilation warnings report."""
    sig = []

    def visit(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        elif isinstance(x, (list, tuple)):
            for y in x:
                visit(y)
        elif isinstance(x, dict):
            for y in x.values():
                visit(y)

    for a in args:
        visit(a)
    return tuple(sig)


class _InstrumentedJit:
    """Transparent wrapper around one jitted callable: every call is a
    ``mx.step.launch`` span (``profiler.span``: the ring, and any live
    device trace); it detects the calls that compiled (``_cache_size``
    growth where available, first-seen aval signature otherwise),
    gives each an ``mx.compile`` span over the same interval, feeds
    the recompile registry + metrics, and warns once per name on
    shape/dtype churn.  Every other attribute (``lower``, …) delegates
    to the wrapped function."""

    def __init__(self, name: str, fn, meta: Optional[dict] = None):
        self._name = name
        self._fn = fn
        self._meta = dict(meta) if meta else {}
        self._seen: set = set()
        with _recompile_lock:
            _recompile.setdefault(name, {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                "avals": [], "last_ms": 0.0})

    def _cache_size(self) -> Optional[int]:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        avals = None
        fresh_sig = False
        if before is None:
            # no cache introspection on this jax: first-seen aval
            # signatures are the detector, so the per-call walk is
            # unavoidable here — with introspection it is skipped
            # (FusedTrainStep.step passes hundreds of param arrays
            # per batch; hashing them every call is pure overhead)
            avals = _avals_of(args)
            fresh_sig = avals not in self._seen
        from . import profiler as _profiler

        with _profiler.span("mx.step.launch", cat="dispatch") as launch:
            out = self._fn(*args, **kwargs)
        t1 = time.perf_counter()
        dur_ms = (t1 - launch.t0) * 1e3
        after = self._cache_size()
        if after is not None and before is not None:
            compiled = after > before
        else:
            compiled = fresh_sig
        if avals is not None:
            self._seen.add(avals)
        if compiled:
            if avals is None:
                avals = _avals_of(args)  # pay the walk on compiles only
            _profiler.record_interval("mx.compile", launch.t0, t1,
                                      cat="compile",
                                      args={"step": self._name})
            self._record_compile(avals, dur_ms)
            try:
                specs = _arg_specs(args)
                with _recompile_lock:
                    _recorded_steps[self._name] = (self, specs,
                                                   self._meta)
            except Exception:
                pass  # audit hook is best-effort, never fails a step
        return out

    def _record_compile(self, avals, dur_ms: float) -> None:
        with _recompile_lock:
            # setdefault, not index: reset_recompile_stats() may have
            # cleared the row seeded by __init__
            st = _recompile.setdefault(self._name, {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                "avals": [], "last_ms": 0.0})
            st["count"] += 1
            st["total_ms"] += dur_ms
            st["last_ms"] = dur_ms
            st["max_ms"] = max(st["max_ms"], dur_ms)
            st["avals"].append([list(s) + [d] for s, d in avals[:8]])
            st["avals"] = st["avals"][-8:]  # keep the recent churn only
            count = st["count"]
            recent = st["avals"]
            warned = _recompile_warned.get(self._name, False)
        try:
            metrics.counter("mxnet_jit_compiles_total",
                            help="XLA compilations of instrumented step "
                                 "functions").inc()
            metrics.gauge("mxnet_jit_compile_ms_last").set(dur_ms)
        except Exception:
            pass
        if count > _warn_threshold() and not warned:
            with _recompile_lock:
                _recompile_warned[self._name] = True
            _log.warning(
                "RECOMPILATION STORM: step function %r compiled %d times "
                "— input shape/dtype churn is forcing jax.jit to retrace "
                "(each compile costs seconds and doubles step time while "
                "it lasts). Recent call avals (shape+dtype per array "
                "arg): %s. Pad/bucketize inputs to a fixed set of shapes "
                "or pin the dtype to stop the churn.",
                self._name, count, recent)

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument_jit(name: str, fn, meta: Optional[dict] = None):
    """Wrap one jitted callable for recompile tracking (dp.py / bulk.py
    step builders).  Idempotent on the name: re-wrapping after a
    rebuild keeps accumulating into the same stats row.  ``meta``
    (e.g. {'compute_dtype': 'bfloat16'}) rides along into
    ``recorded_steps()`` for the static auditor."""
    return _InstrumentedJit(name, fn, meta)


def recompile_stats() -> Dict[str, dict]:
    """{name: {count, total_ms, max_ms, last_ms, avals}} for every
    instrumented step function (plus backend-reported compile time when
    jax.monitoring delivered it)."""
    with _recompile_lock:
        return {k: dict(v) for k, v in _recompile.items()}


def reset_recompile_stats() -> None:
    """Also drops the recorded-step tuples: each pins the LAST wrapper
    (and its compiled executables) per step name for the auditor, so a
    long-lived process that rebuilds steps can release them here."""
    with _recompile_lock:
        _recompile.clear()
        _recompile_warned.clear()
        _recorded_steps.clear()


def _register_jax_monitoring() -> None:
    """Fold the backend's own compile-time events (jax.monitoring
    '/jax/core/compile' family) into the stats where the toolchain
    exposes a listener hook — best-effort, the wrapper above is the
    portable instrument."""
    try:
        from jax._src import monitoring as _mon

        def _listener(event: str, duration: float, **kw):
            if "compile" not in event:
                return
            with _recompile_lock:
                st = _recompile.setdefault("jax_backend:" + event, {
                    "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                    "avals": [], "last_ms": 0.0})
                ms = duration * 1e3
                st["count"] += 1
                st["total_ms"] += ms
                st["last_ms"] = ms
                st["max_ms"] = max(st["max_ms"], ms)

        _mon.register_event_duration_secs_listener(_listener)
    except Exception:
        pass


_register_jax_monitoring()


# ---------------------------------------------------------------------------
# step-metrics registry (gauge / counter / histogram, prom exposition)
# ---------------------------------------------------------------------------
def _prom_name(name: str) -> str:
    out = []
    for i, ch in enumerate(name):
        ok = ch.isalnum() or ch in "_:"
        if ch.isdigit() and i == 0:
            out.append("_")
        out.append(ch if ok else "_")
    return "".join(out)


def _prom_labels(labels: Optional[dict]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        '%s="%s"' % (_prom_name(str(k)),
                     str(v).replace("\\", "\\\\").replace('"', '\\"'))
        for k, v in sorted(labels.items()))
    return "{%s}" % inner


def _fmt(v: float) -> str:
    f = float(v)
    if f != f:
        return "NaN"  # a diverged loss must still export, not crash
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class Gauge:
    """Last-write-wins scalar (step_time, loss, allocator peak)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = "", labels=None):
        self.name, self.help, self.labels = name, help, labels
        self._lock = threading.Lock()
        self.value: Optional[float] = None
        self.updated_ts: Optional[float] = None

    def set(self, value) -> None:
        with self._lock:
            self.value = float(value)
            self.updated_ts = time.time()

    def sample_lines(self) -> List[str]:
        with self._lock:
            v = self.value
        if v is None:
            return []
        return ["%s%s %s" % (_prom_name(self.name),
                             _prom_labels(self.labels), _fmt(v))]

    def to_dict(self) -> dict:
        with self._lock:
            return {"type": "gauge", "value": self.value,
                    "updated_ts": self.updated_ts,
                    "labels": self.labels or None}


class Counter:
    """Monotonic accumulator (samples seen, kvstore bytes, recompiles)."""

    kind = "counter"

    def __init__(self, name: str, help: str = "", labels=None):
        self.name, self.help, self.labels = name, help, labels
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, delta=1) -> None:
        if delta < 0:
            raise ValueError("counters only go up (got %r)" % (delta,))
        with self._lock:
            self.value += float(delta)

    def sample_lines(self) -> List[str]:
        with self._lock:
            v = self.value
        return ["%s%s %s" % (_prom_name(self.name),
                             _prom_labels(self.labels), _fmt(v))]

    def to_dict(self) -> dict:
        with self._lock:
            return {"type": "counter", "value": self.value,
                    "labels": self.labels or None}


# seconds-scale latencies: 1ms .. 60s
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class Histogram:
    """Cumulative-bucket histogram, prom exposition semantics
    (``_bucket{le=...}`` counts are cumulative; ``+Inf`` == count)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", labels=None,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name, self.help, self.labels = name, help, labels
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.buckets) + 1)  # +1: +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, value) -> None:
        v = float(value)
        with self._lock:
            self.sum += v
            self.count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    break
            else:
                self._counts[-1] += 1

    def _cumulative(self) -> List[int]:
        out, acc = [], 0
        for c in self._counts:
            acc += c
            out.append(acc)
        return out

    def percentile(self, q: float) -> Optional[float]:
        """Approximate q-quantile from the bucket upper bounds (the
        straggler analysis' p50/p99)."""
        with self._lock:
            if not self.count:
                return None
            target = q * self.count
            cum = self._cumulative()
        for i, c in enumerate(cum):
            if c >= target:
                return self.buckets[i] if i < len(self.buckets) \
                    else self.buckets[-1]
        return self.buckets[-1]

    def quantile(self, q: float) -> Optional[float]:
        """Interpolated q-quantile — prometheus' histogram_quantile
        semantics (linear within the containing bucket, the +Inf bucket
        clamps to the highest finite bound).  The serving SLO gauges
        (``<name>_p50``/``<name>_p99`` in ``to_prom()``) report this
        rather than :meth:`percentile`'s coarse upper bound."""
        with self._lock:
            if not self.count:
                return None
            target = q * self.count
            cum = self._cumulative()
        prev_cum = 0
        for i, c in enumerate(cum):
            if c >= target:
                if i >= len(self.buckets):
                    return self.buckets[-1]  # +Inf bucket: clamp
                lo = self.buckets[i - 1] if i > 0 else 0.0
                hi = self.buckets[i]
                in_bucket = c - prev_cum
                if in_bucket <= 0:
                    return hi
                return lo + (hi - lo) * (target - prev_cum) / in_bucket
            prev_cum = c
        return self.buckets[-1]

    def sample_lines(self) -> List[str]:
        name = _prom_name(self.name)
        base = dict(self.labels or {})
        with self._lock:
            cum = self._cumulative()
            s, n = self.sum, self.count
        lines = []
        for b, c in zip(self.buckets, cum[:-1]):
            lines.append("%s_bucket%s %d"
                         % (name, _prom_labels({**base, "le": _fmt(b)}), c))
        lines.append("%s_bucket%s %d"
                     % (name, _prom_labels({**base, "le": "+Inf"}), cum[-1]))
        lines.append("%s_sum%s %s" % (name, _prom_labels(self.labels),
                                      _fmt(s)))
        lines.append("%s_count%s %d" % (name, _prom_labels(self.labels), n))
        return lines

    def to_dict(self) -> dict:
        with self._lock:
            return {"type": "histogram", "count": self.count,
                    "sum": self.sum,
                    "buckets": {_fmt(b): c for b, c in
                                zip(self.buckets, self._cumulative()[:-1])},
                    "labels": self.labels or None}


class MetricsRegistry:
    """Named-metric registry with one instance per (name, labels) pair;
    ``to_prom()`` renders the whole registry as Prometheus text
    exposition, ``dump_json()`` as a machine-readable dict, ``flush()``
    writes the MXNET_METRICS_FILE exposition (rate-limited by
    MXNET_METRICS_INTERVAL_S, default 30s; ``force=True`` bypasses)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, frozenset], Any] = {}
        self._last_flush = 0.0
        #: counts the ``clear()`` calls: a caller that holds metrics
        #: across calls (a serving tick's gauges) makes them again when
        #: it moved, so what it writes is still what ``to_prom()`` shows
        self.generation = 0

    def _get(self, cls, name: str, help: str, labels, **kw):
        key = (name, frozenset((labels or {}).items()))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help=help, labels=labels, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError("metric %r already registered as %s"
                                % (name, type(m).__name__))
            return m

    def gauge(self, name: str, help: str = "", labels=None) -> Gauge:
        return self._get(Gauge, name, help, labels)

    def counter(self, name: str, help: str = "", labels=None) -> Counter:
        return self._get(Counter, name, help, labels)

    def histogram(self, name: str, help: str = "", labels=None,
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, labels, buckets=buckets)

    def _sorted(self):
        with self._lock:
            items = list(self._metrics.values())
        return sorted(items, key=lambda m: (m.name,
                                            str(m.labels or "")))

    def to_prom(self) -> str:
        """Prometheus text exposition (one HELP/TYPE block per metric
        name, samples after) — the format node_exporter serves.

        Every histogram additionally exports interpolated ``_p50`` /
        ``_p99`` gauge families (serving SLO reporting needs quantiles
        a scraper can alert on directly, not just cumulative buckets);
        the derived families are grouped after the primary metrics so
        no family's samples interleave."""
        lines: List[str] = []
        seen_hdr = set()
        derived: List[Tuple[str, str, Any, float]] = []
        for m in self._sorted():
            pname = _prom_name(m.name)
            if pname not in seen_hdr:
                seen_hdr.add(pname)
                if m.help:
                    lines.append("# HELP %s %s"
                                 % (pname, m.help.replace("\n", " ")))
                lines.append("# TYPE %s %s" % (pname, m.kind))
            lines.extend(m.sample_lines())
            if isinstance(m, Histogram):
                for q, suffix in ((0.5, "_p50"), (0.99, "_p99")):
                    v = m.quantile(q)
                    if v is not None:
                        derived.append((pname + suffix,
                                        _prom_labels(m.labels), q, v))
        for dname, labels, q, v in sorted(derived,
                                          key=lambda t: (t[0], t[1])):
            if dname not in seen_hdr:
                seen_hdr.add(dname)
                lines.append("# HELP %s interpolated q=%s of %s"
                             % (dname, _fmt(q), dname.rsplit("_p", 1)[0]))
                lines.append("# TYPE %s gauge" % dname)
            lines.append("%s%s %s" % (dname, labels, _fmt(v)))
        return "\n".join(lines) + ("\n" if lines else "")

    def dump_json(self) -> dict:
        out: Dict[str, Any] = {}
        for m in self._sorted():
            d = m.to_dict()
            key = m.name if not m.labels else \
                m.name + _prom_labels(m.labels)
            out[key] = d
        rank, num_workers = _rank_info()
        return {"rank": rank, "num_workers": num_workers,
                "ts": time.time(), "metrics": out}

    def flush(self, path: Optional[str] = None, force: bool = True
              ) -> Optional[str]:
        from . import env as _envmod

        if path is None:
            path = _envmod.get_str("MXNET_METRICS_FILE")
        if not path:
            return None
        # no `or` fallback: MXNET_METRICS_INTERVAL_S=0 legitimately
        # means flush on every step
        interval = _envmod.get_float("MXNET_METRICS_INTERVAL_S", 30.0)
        now = time.time()
        with self._lock:
            if not force and now - self._last_flush < interval:
                return None
            self._last_flush = now
        rank, num_workers = _rank_info()
        if num_workers > 1:
            root, ext = os.path.splitext(path)
            path = "%s_rank%d%s" % (root, rank, ext or ".prom")
        path = _dump_dir_path(path)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(self.to_prom())
            os.replace(tmp, path)  # scrapers never see a torn file
            return path
        except OSError:
            return None

    def maybe_flush(self) -> Optional[str]:
        """Rate-limited flush — the per-step feed calls this so a
        configured MXNET_METRICS_FILE stays fresh without a writer
        thread."""
        return self.flush(force=False)

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()
            self._last_flush = 0.0
            self.generation += 1


#: process-wide registry — fit()/Speedometer/kvstore/io feed it
metrics = MetricsRegistry()


class Held:
    """What ``make(metrics)`` returns — the metrics a hot path writes on
    every call, looked up by name and labels ONCE — kept until the
    process-wide registry is cleared or replaced, then made again."""

    __slots__ = ("_make", "_registry", "_generation", "_value")

    def __init__(self, make):
        self._make = make
        self._registry = self._generation = self._value = None

    def get(self):
        reg = metrics
        if self._registry is not reg or self._generation != reg.generation:
            self._value = self._make(reg)
            self._registry, self._generation = reg, reg.generation
        return self._value


def record_step(step_time_s: float, samples: Optional[int] = None,
                metric_values=None) -> None:
    """One training step's worth of registry updates (fed by fit() and
    FusedTrainStep callers): step-time histogram + gauge, samples/s,
    cumulative sample count, and the evaluation-metric gauges."""
    try:
        metrics.histogram("mxnet_step_time_seconds",
                          help="wall time of one optimizer step"
                          ).observe(step_time_s)
        metrics.gauge("mxnet_step_time_seconds_last").set(step_time_s)
        if samples:
            metrics.counter("mxnet_samples_total",
                            help="training samples consumed").inc(samples)
            if step_time_s > 0:
                metrics.gauge("mxnet_samples_per_second",
                              help="training throughput"
                              ).set(samples / step_time_s)
        for name, value in (metric_values or ()):
            try:
                metrics.gauge("mxnet_train_metric",
                              help="per-batch training metric",
                              labels={"metric": str(name)}).set(value)
            except (TypeError, ValueError):
                pass  # non-scalar metric values have no gauge form
        # every workload that records steps is alive by definition —
        # the supervisor's hung-worker beacon rides the same call
        # (rate-limited + no-op unless supervised)
        touch_heartbeat()
        metrics.maybe_flush()
    except Exception:
        pass  # telemetry must never fail the training loop


def feed_phase_seconds(phase_steps) -> None:
    """``mxnet_step_phase_seconds{phase}`` feed (traceview's ingest
    calls this with the attributed per-step phase durations): one
    histogram family per phase, so a phase regression (backward grew,
    bucket 3's reduce doubled) is scrape-visible with p50/p99 like
    every other histogram here.  ``phase_steps`` maps phase name to a
    list of per-step seconds.  Guarded: telemetry must never fail the
    capture it describes."""
    try:
        for phase, vals in (phase_steps or {}).items():
            h = metrics.histogram(
                "mxnet_step_phase_seconds",
                help="measured device seconds per step phase "
                     "(traceview attribution)",
                labels={"phase": str(phase)})
            for v in vals:
                h.observe(float(v))
        metrics.maybe_flush()
    except Exception:
        pass


def feed_kvstore_bytes(op: str, nbytes: int) -> None:
    """Cumulative ``mxnet_kvstore_bytes_total{op=...}`` feed — the ONE
    place the metric name/help live, shared by kvstore.py's verb fast
    paths and buckets.stamp_profiler.  Guarded so telemetry can never
    fail the collective it measures."""
    try:
        metrics.counter("mxnet_kvstore_bytes_total",
                        help="cumulative kvstore payload bytes",
                        labels={"op": op}).inc(int(nbytes))
    except Exception:
        pass


def feed_io_bytes(nbytes: int) -> None:
    """Cumulative ``mxnet_io_bytes_total`` feed for io.py's fetch path —
    guarded so telemetry can never fail the input pipeline."""
    try:
        metrics.counter("mxnet_io_bytes_total",
                        help="host bytes materialized by the "
                             "input pipeline").inc(int(nbytes))
    except Exception:
        pass


def feed_io_queue_depth(depth: int) -> None:
    """``mxnet_io_queue_depth`` gauge: decoded/placed batches waiting
    ahead of the consumer (io_pipeline's prefetch queue).  Persistently
    0 while step time is io-bound = the decode pool is the bottleneck;
    persistently full = the chip is."""
    try:
        metrics.gauge("mxnet_io_queue_depth",
                      help="input-pipeline prefetch queue depth "
                           "(batches ready ahead of the consumer)"
                      ).set(int(depth))
    except Exception:
        pass


def feed_io_decode_seconds(seconds: float) -> None:
    """``mxnet_io_decode_seconds`` histogram: one decode-pool worker's
    wall time for one batch (shipped with the batch's slot message)."""
    try:
        metrics.histogram("mxnet_io_decode_seconds",
                          help="per-batch decode wall time in the "
                               "input-pipeline worker pool"
                          ).observe(float(seconds))
    except Exception:
        pass


def feed_io_worker_death() -> None:
    """``mxnet_io_worker_deaths_total``: decode workers that died and
    whose shard the parent adopted inline (degraded, not hung)."""
    try:
        metrics.counter("mxnet_io_worker_deaths_total",
                        help="decode-pool workers that died "
                             "(shard adopted inline by the parent)"
                        ).inc()
    except Exception:
        pass


def samples_per_second() -> Optional[float]:
    """The registry's current samples/s gauge (Speedometer's fallback
    when its own wall-clock interval is below clock resolution)."""
    g = metrics.gauge("mxnet_samples_per_second")
    return g.value


def sample_allocator_peak() -> None:
    """Fold the allocator's peak bytes into the registry (fed on
    Speedometer fires — cheap enough there, too hot for every step on
    backends that fall back to live-buffer accounting)."""
    try:
        from . import profiler as _profiler

        m = _profiler._memory_bytes()
        if m is None:
            return
        in_use, peak = m
        metrics.gauge("mxnet_memory_bytes_in_use",
                      help="device allocator bytes in use").set(in_use)
        metrics.gauge("mxnet_memory_peak_bytes",
                      help="device allocator peak bytes").set(peak)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# prom-text validation (used by the self-test and tests)
# ---------------------------------------------------------------------------
def validate_prom_text(text: str) -> List[str]:
    """Validate Prometheus text-exposition syntax + histogram
    invariants; returns a list of problems (empty == valid)."""
    import re

    problems: List[str] = []
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
        r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
        r" (NaN|[+-]?Inf|[+-]?[0-9.eE+-]+)$")
    label_re = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=\"([^\"]*)\"")

    def label_key(labels: str, drop: str = "le") -> frozenset:
        return frozenset((k, v) for k, v in label_re.findall(labels or "")
                         if k != drop)

    typed: Dict[str, str] = {}
    hist_counts: Dict[Tuple[str, frozenset], float] = {}
    hist_inf: Dict[Tuple[str, frozenset], float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            problems.append("line %d: empty line" % lineno)
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                    "gauge", "counter", "histogram", "summary", "untyped"):
                problems.append("line %d: bad TYPE line" % lineno)
            else:
                typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue
        m = sample_re.match(line)
        if not m:
            problems.append("line %d: unparsable sample %r" % (lineno, line))
            continue
        name, labels = m.group(1), m.group(2) or ""
        value = float(m.group(3).replace("Inf", "inf"))
        if name.endswith("_count") and typed.get(name[:-6]) == "histogram":
            hist_counts[(name[:-6], label_key(labels))] = value
        if name.endswith("_bucket") and 'le="+Inf"' in labels:
            hist_inf[(name[:-7], label_key(labels))] = value
    for key, count in hist_counts.items():
        # exposition contract: the +Inf bucket equals _count
        inf = hist_inf.get(key)
        if inf is None:
            problems.append("histogram %s: no +Inf bucket" % (key,))
        elif inf != count:
            problems.append("histogram %s: +Inf bucket %s != count %s"
                            % (key, inf, count))
    return problems


# ---------------------------------------------------------------------------
# CLI: python -m mxnet_tpu.diagnostics --self-test
# (mirrors python -m mxnet_tpu.parallel.overlap --self-test)
# ---------------------------------------------------------------------------
def _self_test() -> Tuple[bool, Dict[str, bool]]:
    import tempfile

    checks: Dict[str, bool] = {}

    # 1) ring-buffer wraparound: 20 entries through capacity 8 keeps the
    # LAST 8, drops 12, seqs stay monotonic
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        with_seq = fr.start("push", keys=["k%d" % i], nbytes=64,
                            dtype="float32")
        fr.complete(with_seq)
    header, entries = fr.snapshot()
    seqs = [e["seq"] for e in entries]
    checks["ring_len==capacity"] = len(entries) == 8
    checks["ring_dropped==12"] = header["dropped"] == 12
    checks["ring_keeps_latest"] = seqs == list(range(12, 20))
    checks["ring_all_completed"] = all(e["state"] == "completed"
                                       for e in entries)

    # 2) suspect marking: an entry left in flight past the timeout
    fr2 = FlightRecorder(capacity=8)
    fr2.start("allreduce", bucket=7, keys=["w3"], nbytes=1 << 20,
              dtype="float32")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "wd.json")
        orig_dump_path = fr2.dump_path
        fr2.dump_path = lambda base=None: path
        try:
            time.sleep(0.02)
            n = fr2.check_timeouts(0.01)
        finally:
            fr2.dump_path = orig_dump_path
        checks["watchdog_suspect"] = n == 1
        try:
            with open(path) as f:
                wd = json.load(f)
            checks["watchdog_dumped"] = (
                wd["header"]["reason"] == "watchdog_timeout"
                and wd["entries"][0]["state"] == "suspect"
                and wd["entries"][0]["bucket"] == 7)
        except OSError:
            checks["watchdog_dumped"] = False

    # 3) signal-handler dump: SIGUSR1 to self persists the ring and the
    # process lives on
    ok_sig = False
    if hasattr(signal, "SIGUSR1"):
        with tempfile.TemporaryDirectory() as d:
            fr3 = FlightRecorder(capacity=4)
            s = fr3.start("push", keys=["sig"], nbytes=8, dtype="float32")
            fr3.complete(s)
            path = os.path.join(d, "sig.json")
            fr3.dump_path = lambda base=None: path
            if fr3.install_signal_handlers():
                os.kill(os.getpid(), signal.SIGUSR1)
                deadline = time.time() + 2.0
                while time.time() < deadline and not os.path.exists(path):
                    time.sleep(0.01)
                try:
                    with open(path) as f:
                        sig_payload = json.load(f)
                    ok_sig = (sig_payload["header"]["reason"] == "SIGUSR1"
                              and len(sig_payload["entries"]) == 1)
                except (OSError, ValueError):
                    ok_sig = False
    checks["signal_dump"] = ok_sig

    # 4) prom-text rendering validates
    reg = MetricsRegistry()
    reg.gauge("selftest_loss", help="loss").set(1.5)
    reg.counter("selftest_samples_total", help="samples").inc(256)
    h = reg.histogram("selftest_step_seconds", help="step time")
    for v in (0.004, 0.009, 0.02, 0.02, 3.0):
        h.observe(v)
    text = reg.to_prom()
    problems = validate_prom_text(text)
    checks["prom_valid"] = not problems
    checks["prom_histogram_count"] = (
        "selftest_step_seconds_count 5" in text)
    # derived quantile gauges: interpolated p50/p99 families present,
    # typed gauge, and the p50 lands inside its containing bucket
    # (0.01 < p50 <= 0.025 for observations 0.004/0.009/0.02/0.02/3.0)
    checks["prom_quantile_gauges"] = (
        "# TYPE selftest_step_seconds_p50 gauge" in text
        and "selftest_step_seconds_p99" in text)
    p50 = h.quantile(0.5)
    checks["quantile_interpolates"] = p50 is not None \
        and 0.01 < p50 <= 0.025
    js = reg.dump_json()
    checks["json_dump"] = (
        js["metrics"]["selftest_loss"]["value"] == 1.5
        and js["metrics"]["selftest_samples_total"]["value"] == 256.0)

    return all(checks.values()), checks


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.diagnostics",
        description="flight recorder / runtime health self-test + dump")
    ap.add_argument("--self-test", action="store_true",
                    help="exercise ring wraparound, watchdog + signal "
                         "dumps, prom rendering")
    ap.add_argument("--dump", action="store_true",
                    help="dump this process's flight recorder now")
    args = ap.parse_args(argv)
    if args.self_test:
        ok, checks = _self_test()
        print(json.dumps({"self_test_ok": ok, "checks": checks}))
        return 0 if ok else 1
    if args.dump:
        print(dump() or "")
        return 0
    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
