"""mx.profiler — the runtime telemetry subsystem.

ref: python/mxnet/profiler.py:27-58 (set_config/set_state/dump_profile),
src/engine/profiler.{h,cc} (OprExecStat stamped around every executed op,
DumpProfile emits "traceEvents" JSON, profiler.cc:155), and the 1.x
aggregate-stats surface (MXAggregateProfileStatsPrint -> ``dumps``,
src/profiler/aggregate_stats.cc) plus the Counter/Marker object API
(python/mxnet/profiler.py Counter/Marker/Domain).

Four layers, all TPU-native:
  * **Python-side op events**: `mx.nd` invokes, Executor
    forward/backward spans, kvstore comms, data-IO fetches and
    optimizer updates are stamped here.  Because XLA dispatch is async
    (the python call returns before the TPU finishes — SURVEY.md §3.1),
    accurate per-op durations require synchronizing after each op;
    `set_config(profile_sync=True)` (default) blocks on each op's
    output the way `MXNET_ENGINE_TYPE=NaiveEngine` degrades the
    reference engine to synchronous execution for debugging.
  * **Aggregate stats**: every span/counter also folds into per-name
    count/total/min/max accumulators; `dumps()` renders the
    reference-style table, `summary()` the machine-readable dict.
  * **Memory + comms counters**: `set_config(profile_memory=True)`
    samples the device allocator (`memory_stats()`, falling back to
    live-buffer accounting on backends without allocator stats — the
    CPU test mesh) into chrome `ph:"C"` counter tracks; kvstore and io
    stamp cumulative bytes-on-the-wire counters.
  * **XLA device traces**: `set_config(profile_xla=True)` additionally
    drives `jax.profiler.start_trace/stop_trace` so the real device
    timeline (fusions, collectives, HBM traffic) lands in TensorBoard
    format next to the chrome trace.

Multi-worker runs: each rank dumps ``<base>_rank{K}.json`` with
``pid = rank`` (merge with ``tools/merge_traces.py``), and
``MXNET_PROFILER_AUTOSTART=1`` (reference env parity) makes worker
subprocesses self-start tracing at import and dump at exit.
"""
from __future__ import annotations

import atexit
import collections
import json
import os
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from .traceview import capture as _capture

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dump_profile", "dumps",
           "summary", "pause", "resume", "is_running", "record_span",
           "record_counter", "record_marker", "record_bytes", "span",
           "Span", "record_interval", "nest", "unnest", "spans_between",
           "RING_SPANS",
           "Domain", "Counter", "Marker", "set_rank", "sample_memory"]

# an RLock: the stamping helpers call each other (record_bytes ->
# record_counter, record_span -> _tid) while holding it
_lock = threading.RLock()
_events: List[dict] = []
_state = "stop"
_paused = False
_filename = "profile.json"
_sync = True
_xla = False
_xla_dir: Optional[str] = None
_memory = False
_t0 = None
# aggregate accumulators: (cat, name) -> [count, total, min, max]
# (span durations in us; counter/byte values in their own units)
_span_stats: Dict[Tuple[str, str], List[float]] = {}
_counter_stats: Dict[Tuple[str, str], List[float]] = {}
# cumulative byte tallies for record_bytes counters
_byte_totals: Dict[str, int] = {}
# python thread ident -> small sequential tid (+ name for metadata);
# the reference trace carries real engine-thread ids, not tid=0
_tids: Dict[int, int] = {}
_tid_names: Dict[int, str] = {}
# explicit rank override (set by dist kvstore creation; env otherwise)
_rank_override: Optional[Tuple[int, int]] = None
# peak tracker for the live-buffer memory fallback (CPU backend)
_mem_peak = 0


class Span(NamedTuple):
    """One finished span of the ring: ``t0``/``t1`` are
    ``time.perf_counter()`` seconds, unshifted; ``thread`` is the small
    id of :func:`_tid`; ``depth`` counts the spans open on that thread
    when this one opened, so a span's parent is the enclosing span of
    depth - 1 on the same thread."""
    name: str
    t0: float
    t1: float
    thread: int
    depth: int
    args: Optional[dict]


#: spans the ring keeps; the oldest fall out (a 50 s window of the
#: fastest loop here, a 10 ms decode tick, is under a tenth of it)
RING_SPANS = 65536
# every span, whether or not a profiling session runs: deque.append is
# atomic, so writers on several threads need no lock
_ring: "collections.deque[Span]" = collections.deque(maxlen=RING_SPANS)
_nesting = threading.local()


def is_running() -> bool:
    return _state == "run" and not _paused


def profiling_state() -> Tuple[bool, bool]:
    """(running, sync) read under one lock acquisition — callers that
    stamp an op span need both decisions from the SAME config snapshot
    (a concurrent set_config between the two reads must not split
    them)."""
    with _lock:
        return (_state == "run" and not _paused, _sync)


def sync_enabled() -> bool:
    with _lock:
        return _state == "run" and not _paused and _sync


def memory_enabled() -> bool:
    with _lock:
        return _state == "run" and not _paused and _memory


def set_config(profile_all=False, profile_symbolic=False,
               profile_imperative=False, profile_memory=False,
               profile_api=False, filename="profile.json",
               profile_sync=True, profile_xla=False, xla_trace_dir=None,
               aggregate_stats=True, **kwargs):
    """ref: profiler.py:27 set_config. The reference's mode flags select
    which subsystems stamp events; here symbolic+imperative are both
    python-side and always stamped, the flags are accepted for API
    compatibility.  ``aggregate_stats`` is likewise always-on (the
    accumulators are cheap) and accepted for parity.

    ``profile_memory=True`` samples allocator bytes-in-use/peak into
    counter tracks around executor forward/backward.

    XLA device tracing is deliberately opt-in: it starts only with
    ``profile_xla=True``, or with ``profile_all=True`` when an
    ``xla_trace_dir`` is ALSO given (profile_all alone must not spray
    TensorBoard dumps into a derived directory — the 1.x flag never
    implied device tracing)."""
    global _filename, _sync, _xla, _xla_dir, _memory
    with _lock:
        _filename = filename
        _sync = bool(profile_sync)
        _memory = bool(profile_memory)
        _xla = bool(profile_xla or (profile_all and xla_trace_dir is not None))
        _xla_dir = xla_trace_dir


profiler_set_config = set_config  # legacy alias (ref: profiler.py:27)


def set_state(state="stop"):
    """'run' | 'stop' (ref: profiler.py:42 set_state →
    MXSetProfilerState)."""
    global _state, _t0, _mem_peak
    assert state in ("run", "stop")
    stopped_run = False
    with _lock:
        if state == "run" and _state != "run":
            _events.clear()
            _span_stats.clear()
            _counter_stats.clear()
            _byte_totals.clear()
            _mem_peak = 0
            _t0 = time.perf_counter_ns()
            if _xla:
                # traceview owns the ONE sanctioned jax.profiler site
                # (mxlint MXL009) — this path routes through it
                from .traceview import capture as _tvcap

                _tvcap.start_device_trace(
                    _xla_dir or os.path.splitext(_filename)[0] + "_xla")
        elif state == "stop" and _state == "run":
            if _xla:
                from .traceview import capture as _tvcap

                _tvcap.stop_device_trace()
            stopped_run = True
        _state = state
    if stopped_run:
        # the 1.x profiler persisted the trace on stop/shutdown — old
        # example code (example/profiler/profiler_matmul.py) never
        # calls dump and expects the file to exist afterwards.  Only
        # the run->stop TRANSITION dumps: a redundant stop must not
        # clobber a previously dumped trace with an empty one
        dump(finished=False)


profiler_set_state = set_state


def pause():
    """Suspend event collection without ending the session
    (ref: MXProfilePause).  Takes the lock: an unlocked write could be
    reordered against a concurrent record_span's state check."""
    global _paused
    with _lock:
        _paused = True


def resume():
    global _paused
    with _lock:
        _paused = False


def set_rank(rank: Optional[int], num_workers: int = 1) -> None:
    """Pin this process's worker rank for trace dumps.  Called by the
    dist kvstore once the scheduler assigns a rank; env
    (DMLC_WORKER_ID / MXNET_PROCESS_ID) covers processes that never
    create a store.  The pin outlives the store on purpose — a process
    that WAS rank K keeps dumping rank-K traces (the autostart atexit
    dump runs after kv.close()); pass ``rank=None`` to clear it."""
    global _rank_override
    with _lock:
        _rank_override = None if rank is None else \
            (int(rank), int(num_workers))


def _dist_info() -> Tuple[int, int]:
    """(rank, num_workers) — explicit set_rank wins, then the launcher
    env contracts (tools/launch.py sets DMLC_WORKER_ID per worker;
    dist.py's jax pod contract sets MXNET_PROCESS_ID)."""
    if _rank_override is not None:
        return _rank_override
    if os.environ.get("DMLC_WORKER_ID") is not None:
        return (int(os.environ["DMLC_WORKER_ID"]),
                int(os.environ.get("DMLC_NUM_WORKER", "1")))
    from . import env as _env

    pid = _env.get_str("MXNET_PROCESS_ID", None)
    if pid is not None:
        return int(pid), _env.get_int("MXNET_NUM_PROCESSES")
    return 0, 1


def _now_us() -> float:
    return (time.perf_counter_ns() - (_t0 or time.perf_counter_ns())) / 1e3


def _session_us(t: float) -> float:
    """A ``perf_counter()`` reading on the chrome dump's clock
    (microseconds since the session's ``set_state('run')``)."""
    return t * 1e6 - (_t0 or 0) / 1e3


def _tid() -> int:
    """Small sequential id for the calling thread (the chrome trace's
    tid lane); names are kept for dump-time thread_name metadata."""
    ident = threading.get_ident()
    tid = _tids.get(ident)
    if tid is None:
        with _lock:
            tid = _tids.setdefault(ident, len(_tids))
            _tid_names.setdefault(tid, threading.current_thread().name)
    return tid


def register_tid_name(tid: int, name: str) -> None:
    """Claim a trace lane for an EXTERNAL actor (a decode-pool worker
    process stamping through the parent, io_pipeline.py): the lane gets
    thread_name metadata in the dump without a backing Python thread.
    Callers should pick tids >= io_pipeline.IO_WORKER_TID_BASE so the
    sequential thread ids never collide with them."""
    with _lock:
        _tid_names.setdefault(int(tid), str(name))


def _fold(stats: Dict[Tuple[str, str], List[float]], key: Tuple[str, str],
          value: float) -> None:
    st = stats.get(key)
    if st is None:
        stats[key] = [1, value, value, value]
    else:
        st[0] += 1
        st[1] += value
        if value < st[2]:
            st[2] = value
        if value > st[3]:
            st[3] = value


def record_span(name: str, start_us: float, dur_us: float,
                cat: str = "operator", tid: Optional[int] = None,
                args: Optional[dict] = None):
    """Stamp one complete ('ph':'X') event (ref: OprExecStat →
    traceEvents, profiler.cc:155) and fold it into the aggregate
    accumulators.  The state check happens under the same lock as the
    append, so a concurrent set_state cannot interleave."""
    with _lock:
        if _state != "run" or _paused:
            return
        ev = {"name": name, "cat": cat, "ph": "X", "ts": start_us,
              "dur": dur_us, "pid": 0,
              "tid": _tid() if tid is None else tid}
        if args:
            ev["args"] = dict(args)
        _events.append(ev)
        _fold(_span_stats, (cat, name), dur_us)


def record_counter(name: str, value, cat: str = "counter",
                   tid: Optional[int] = None):
    """Stamp a chrome counter sample ('ph':'C', ref: the 1.x profiler's
    Counter objects dumping value tracks)."""
    with _lock:
        if _state != "run" or _paused:
            return
        _events.append({"name": name, "cat": cat, "ph": "C",
                        "ts": _now_us(), "pid": 0,
                        "tid": _tid() if tid is None else tid,
                        "args": {name: value}})
        _fold(_counter_stats, (cat, name), float(value))


def record_marker(name: str, cat: str = "marker", scope: str = "process"):
    """Stamp an instant event ('ph':'i'; ref: profiler.py Marker.mark).
    scope: 'global' | 'process' | 'thread'."""
    with _lock:
        if _state != "run" or _paused:
            return
        _events.append({"name": name, "cat": cat, "ph": "i",
                        "ts": _now_us(), "pid": 0, "tid": _tid(),
                        "s": {"global": "g", "process": "p",
                              "thread": "t"}.get(scope, "p")})


def nd_nbytes(arr) -> int:
    """Buffer bytes of one array-like (anything with .shape/.dtype) —
    the shared core of the kvstore and io byte counters.  Telemetry
    only: returns 0 instead of raising."""
    import numpy as _np

    try:
        n = 1
        for d in arr.shape:
            n *= int(d)
        return n * _np.dtype(arr.dtype).itemsize
    except Exception:
        return 0


def record_bytes(name: str, nbytes: int, cat: str = "comms"):
    """Cumulative byte tally as a counter track — kvstore push/pull and
    io batch fetches report bytes-on-the-wire through this."""
    with _lock:
        if _state != "run" or _paused:
            return
        total = _byte_totals.get(name, 0) + int(nbytes)
        _byte_totals[name] = total
        record_counter(name, total, cat=cat)


# ---------------------------------------------------------------------------
# object API (ref: python/mxnet/profiler.py Domain/Counter/Marker)
# ---------------------------------------------------------------------------
class Domain:
    """Named grouping for Counter/Marker tracks (ref: profiler.py
    Domain → MXProfileCreateDomain); becomes the chrome 'cat'."""

    def __init__(self, name: str):
        self.name = name

    def new_counter(self, name, value=None) -> "Counter":
        return Counter(self, name, value)

    def new_marker(self, name) -> "Marker":
        return Marker(self, name)

    def __str__(self):
        return self.name


def _domain_name(domain) -> str:
    if domain is None:
        return "counter"
    return domain.name if isinstance(domain, Domain) else str(domain)


class Counter:
    """Value-tracking counter stamping 'ph':'C' events on every change
    (ref: profiler.py Counter → MXProfileCreateCounter)."""

    def __init__(self, domain=None, name: str = "counter", value=None):
        self._cat = _domain_name(domain)
        self._name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        # stamp inside the same lock hold: two racing updates must land
        # in the trace in value order (the lock is re-entrant)
        with _lock:
            self._value = value
            record_counter(self._name, value, cat=self._cat)

    def increment(self, delta=1):
        with _lock:
            self._value += delta
            record_counter(self._name, self._value, cat=self._cat)

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.increment(-delta)
        return self

    @property
    def value(self):
        return self._value


class Marker:
    """Instant-event marker (ref: profiler.py Marker →
    MXProfileCreateMarker / mark())."""

    def __init__(self, domain=None, name: str = "marker"):
        self._cat = _domain_name(domain)
        self._name = name

    def mark(self, scope: str = "process"):
        record_marker(self._name, cat=self._cat, scope=scope)


class span:
    """Context manager around a python-side region.  Every span goes to
    three places: the ring (:func:`spans_between`), on the clock of
    ``time.perf_counter()``; whatever ``jax.profiler`` session is live
    (the operator's, ``MXNET_TRACE_DIR``'s or a benchmark's), as a host
    annotation of the same name through ``traceview.capture``; and the
    chrome event list while ``mx.profiler`` runs.  ``step`` makes the
    annotation a step marker carrying that step number."""

    __slots__ = ("name", "cat", "args", "step", "t0", "depth", "_note")

    def __init__(self, name: str, cat: str = "operator",
                 args: Optional[dict] = None, step: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.step = step

    def __enter__(self):
        self.depth = getattr(_nesting, "depth", 0)
        _nesting.depth = self.depth + 1
        if self.step is None:
            self._note = _capture.annotation(self.name,
                                             **(self.args or {}))
        else:
            self._note = _capture.step_annotation(self.name, self.step)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        _nesting.depth = self.depth
        record_interval(self.name, self.t0, t1, self.cat, self.args,
                        depth=self.depth)
        return False


def record_interval(name: str, t0: float, t1: float,
                    cat: str = "operator", args: Optional[dict] = None,
                    depth: Optional[int] = None) -> None:
    """A span whose two ``perf_counter()`` readings the caller already
    holds (a compile is known for one only when the call returns): to
    the ring and the chrome list, but into no device trace."""
    if depth is None:
        depth = getattr(_nesting, "depth", 0)
    _ring.append(Span(name, t0, t1, _tid(), depth, args))
    if _state == "run":
        record_span(name, _session_us(t0), (t1 - t0) * 1e6, cat,
                    args=args)


def nest() -> int:
    """Open one level of this thread's span nesting, as :class:`span`
    does, with no annotation and no record: the spans opened until
    :func:`unnest` sit one level under an interval that the caller
    records itself (:func:`record_interval` with the depth returned)."""
    depth = getattr(_nesting, "depth", 0)
    _nesting.depth = depth + 1
    return depth


def unnest(depth: int) -> None:
    """Close the level :func:`nest` opened: ``depth`` is what it gave."""
    _nesting.depth = depth


def spans_between(t0: float, t1: float) -> List[Span]:
    """The ring's spans that overlap ``[t0, t1]`` (``perf_counter()``
    seconds), oldest first."""
    return [s for s in list(_ring) if s.t1 >= t0 and s.t0 <= t1]


# ---------------------------------------------------------------------------
# memory profiling (set_config(profile_memory=True))
# ---------------------------------------------------------------------------
def _memory_bytes() -> Optional[Tuple[int, int]]:
    """(bytes_in_use, peak_bytes_in_use) from the device allocator
    (TPU/GPU expose memory_stats()); backends without allocator stats
    (the CPU test mesh returns None) fall back to summing live jax
    buffers, with the peak tracked per profiling session."""
    global _mem_peak
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
        if stats:
            in_use = int(stats.get("bytes_in_use", 0))
            peak = int(stats.get("peak_bytes_in_use", in_use))
            return in_use, peak
        in_use = sum(int(getattr(a, "nbytes", 0) or 0)
                     for a in jax.live_arrays())
        with _lock:
            _mem_peak = max(_mem_peak, in_use)
            peak = _mem_peak
        return in_use, peak
    except Exception:
        return None  # a telemetry sample must never fail the caller


def sample_memory():
    """Stamp the allocator's bytes-in-use / peak as counter events —
    called by the executor around forward/backward spans when
    profile_memory is enabled (ref: profile_memory in the 1.x
    set_config; the reference sampled its pooled storage managers)."""
    if not memory_enabled():
        return
    m = _memory_bytes()
    if m is None:
        return
    in_use, peak = m
    record_counter("memory:bytes_in_use", in_use, cat="memory")
    record_counter("memory:peak_bytes_in_use", peak, cat="memory")


# ---------------------------------------------------------------------------
# dumps
# ---------------------------------------------------------------------------
def dump(finished=True):
    """Write the chrome://tracing JSON (ref: profiler.py:53 dump_profile
    → MXDumpProfile; format per profiler.cc:155 DumpProfile).

    Multi-worker runs write ``<base>_rank{K}<ext>`` with every event's
    pid set to the rank (one process lane per worker after
    tools/merge_traces.py)."""
    rank, num_workers = _dist_info()
    with _lock:
        fname = _filename
        if num_workers > 1:
            base, ext = os.path.splitext(fname)
            fname = "%s_rank%d%s" % (base, rank, ext or ".json")
        if not os.path.isabs(fname):
            # relative trace dumps land under MXNET_DUMP_DIR like the
            # flight-recorder/metrics artifacts (diagnostics.py) so
            # test/bench runs stop littering the CWD
            from . import diagnostics as _diag

            fname = _diag._dump_dir_path(fname)
        events = [dict(e, pid=rank) for e in _events]
        meta = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
                 "args": {"name": "rank %d" % rank}}]
        meta += [{"name": "thread_name", "ph": "M", "pid": rank, "tid": t,
                  "args": {"name": n}} for t, n in sorted(_tid_names.items())]
        payload = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        with open(fname, "w") as f:
            json.dump(payload, f)
        if finished:
            _events.clear()
    return fname


dump_profile = dump


def summary(reset: bool = False) -> dict:
    """Machine-readable aggregate stats: ``{"spans": {cat: {name:
    {count,total_ms,min_ms,max_ms,avg_ms}}}, "counters": {cat: {name:
    {count,min,max,avg}}}}`` — the dict behind :func:`dumps`."""
    with _lock:
        spans = {k: list(v) for k, v in _span_stats.items()}
        counters = {k: list(v) for k, v in _counter_stats.items()}
        if reset:
            # aggregates only — _byte_totals is the LIVE cumulative
            # baseline of the still-recording counter tracks; clearing
            # it mid-session would saw-tooth the chrome counters
            _span_stats.clear()
            _counter_stats.clear()
    out: dict = {"spans": {}, "counters": {}}
    for (cat, name), (count, total, mn, mx) in spans.items():
        out["spans"].setdefault(cat, {})[name] = {
            "count": int(count), "total_ms": total / 1e3,
            "min_ms": mn / 1e3, "max_ms": mx / 1e3,
            "avg_ms": total / count / 1e3}
    for (cat, name), (count, total, mn, mx) in counters.items():
        out["counters"].setdefault(cat, {})[name] = {
            "count": int(count), "min": mn, "max": mx,
            "avg": total / count}
    out["phases"] = _phase_table(out["spans"])
    return out


def _phase_table(spans: dict) -> list:
    """Per-phase rows [{phase, total_s, pct_of_step, p50_s, p99_s,
    source}] — from traceview's MEASURED device attribution when this
    process completed a capture, else plain span aggregation (one row
    per span category, host-side wall)."""
    try:
        from . import traceview as _tv

        tvs = _tv.last_summary()
    except Exception:
        tvs = None
    if tvs:
        rows = []
        for phase, v in (tvs.get("phases") or {}).items():
            rows.append({
                "phase": phase, "total_s": v.get("total_s"),
                "pct_of_step": v.get("pct_of_step"),
                "p50_s": v.get("p50_s"), "p99_s": v.get("p99_s"),
                "source": "trace"})
        rows.sort(key=lambda r: -(r["total_s"] or 0.0))
        return rows
    step_total = sum(s["total_ms"]
                     for s in (spans.get("step") or {}).values())
    rows = []
    for cat, names in spans.items():
        tot_ms = sum(s["total_ms"] for s in names.values())
        rows.append({
            "phase": cat, "total_s": tot_ms / 1e3,
            "pct_of_step": (tot_ms / step_total * 100.0)
            if step_total else None,
            "p50_s": None, "p99_s": None, "source": "spans"})
    rows.sort(key=lambda r: -(r["total_s"] or 0.0))
    return rows


def dumps(reset: bool = False) -> str:
    """Aggregate per-op stats table (ref: profiler.py dumps →
    MXAggregateProfileStatsPrint; format per
    src/profiler/aggregate_stats.cc DumpTable)."""
    stats = summary(reset=reset)
    lines = ["Profile Statistics.",
             "\tNote that counter items are counter values "
             "and not time units."]
    hdr = ("%-40s %12s %16s %16s %16s %16s"
           % ("Name", "Total Count", "Time (ms)", "Min Time (ms)",
              "Max Time (ms)", "Avg Time (ms)"))
    rule = ("%-40s %12s %16s %16s %16s %16s"
            % ("----", "-----------", "---------", "-------------",
               "-------------", "-------------"))
    for cat in sorted(stats["spans"]):
        lines += ["", cat, "=" * 17, hdr, rule]
        for name in sorted(stats["spans"][cat]):
            s = stats["spans"][cat][name]
            lines.append("%-40s %12d %16.4f %16.4f %16.4f %16.4f"
                         % (name[:40], s["count"], s["total_ms"],
                            s["min_ms"], s["max_ms"], s["avg_ms"]))
    chdr = ("%-40s %12s %16s %16s %16s"
            % ("Name", "Total Count", "Min Value", "Max Value",
               "Avg Value"))
    crule = ("%-40s %12s %16s %16s %16s"
             % ("----", "-----------", "---------", "---------",
                "---------"))
    for cat in sorted(stats["counters"]):
        lines += ["", cat + " (counters)", "=" * 17, chdr, crule]
        for name in sorted(stats["counters"][cat]):
            s = stats["counters"][cat][name]
            lines.append("%-40s %12d %16.1f %16.1f %16.1f"
                         % (name[:40], s["count"], s["min"], s["max"],
                            s["avg"]))
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# shared shutdown path: ONE atexit hook persists every telemetry
# artifact a dying rank owes the post-mortem — the chrome trace (when a
# profiling session is still running) AND the collective flight
# recorder + metrics exposition (diagnostics.py).  Registered at import
# unconditionally: before this, only the AUTOSTART path registered a
# dump and only the trace was covered, so a rank that died mid-run left
# no flight-recorder evidence for merge_traces --health.
# ---------------------------------------------------------------------------
def _shutdown():
    try:
        if _state == "run":
            set_state("stop")  # run->stop transition persists the trace
    except Exception:
        pass  # e.g. the configured dump dir is already gone at exit
    finally:
        # flight-recorder + metrics leg — only if diagnostics was ever
        # imported (nothing to dump otherwise); its own gating decides
        # whether a file is actually written
        diag = sys.modules.get(__package__ + ".diagnostics")
        if diag is not None:
            try:
                diag._atexit_dump()
            except Exception:
                pass


atexit.register(_shutdown)


# ---------------------------------------------------------------------------
# MXNET_PROFILER_AUTOSTART env parity (ref: the 1.x env of the same
# name): worker subprocesses (tests/dist_worker.py et al.) self-start
# tracing at import and persist their rank trace at interpreter exit
# (via the shared _shutdown hook above).
# ---------------------------------------------------------------------------
def _autostart():
    # registered import_time=True in env.py: the autostart contract IS
    # an import-time read (worker subprocesses self-start tracing)
    from . import env as _env

    if not _env.get_bool("MXNET_PROFILER_AUTOSTART"):
        return
    set_config(profile_all=True,
               filename=_env.get_str("MXNET_PROFILER_FILENAME"))
    set_state("run")


_autostart()
