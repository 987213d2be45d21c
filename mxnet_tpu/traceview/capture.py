"""The ONE sanctioned ``jax.profiler`` capture site (mxlint MXL009
rejects direct use anywhere else in ``mxnet_tpu/``).

Two layers:

  * thin wrappers (:func:`start_device_trace` / :func:`stop_device_trace`
    / :func:`annotation` / :func:`step_annotation`) — profiler.py's
    ``profile_xla`` path, every ``profiler.span`` (the ``mx.*`` spans
    of the train steps and the generation engine) and the step tracer
    below all route through these, so the repo has exactly one module
    touching ``jax.profiler``;
  * the env-armed step tracer — ``MXNET_TRACE_DIR`` +
    ``MXNET_TRACE_STEPS`` record N steady-state dispatch windows of
    whatever workload dispatches first (FusedTrainStep /
    TransformerTrainStep / bulk fit / serving dispatch), bracket each
    with a ``mxnet:step:<i>:k=<k>`` annotation, then stop, run the
    jax-free attribution (parse.py) against the stamped bucket plan +
    flight-recorder entries, write ``traceview_summary_rank{K}.json``
    into the trace dir and feed ``mxnet_step_phase_seconds{phase}``.

The first armed dispatch is skipped (untraced warmup) so compile time
never pollutes the steady-state measurement.  Everything is guarded:
tracing must never fail the step it measures.
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from typing import Any, Optional

_log = logging.getLogger("mxnet_tpu.traceview")

__all__ = ["start_device_trace", "stop_device_trace", "annotation",
           "step_annotation", "step_window", "enabled", "last_summary", "last_summary_path",
           "reset"]

#: armed dispatches skipped before the trace starts (compile absorber)
WARMUP_DISPATCHES = 1

_UNREAD = object()


def start_device_trace(trace_dir: str) -> None:
    """Sanctioned ``jax.profiler.start_trace`` wrapper."""
    import jax

    jax.profiler.start_trace(trace_dir)


def stop_device_trace() -> None:
    """Sanctioned ``jax.profiler.stop_trace`` wrapper."""
    import jax

    jax.profiler.stop_trace()


def annotation(name: str, **attrs):
    """Sanctioned ``jax.profiler.TraceAnnotation`` constructor — the
    host-side marker the parser's step windows come from, and what
    puts every ``profiler.span`` into a live device trace (``attrs``
    ride along as the event's arguments)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


def step_annotation(name: str, step: int):
    """Sanctioned ``jax.profiler.StepTraceAnnotation`` constructor —
    the step marker xprof's step view groups device work by."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=int(step))


class StepTracer:
    """Single-shot, env-armed capture of N dispatch windows."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dispatches = 0      # armed dispatches seen (incl. warmup)
        self._recorded = 0        # traced windows completed
        self._tracing = False
        self._done = False
        self._t_capture0: Optional[float] = None
        self._workload: Optional[str] = None
        self._summary: Optional[dict] = None
        self._summary_path: Optional[str] = None
        self._cfg: Any = _UNREAD

    # -- config: read once per tracer, on the first dispatch; whoever
    # changes the environment afterwards re-arms with ``reset()`` ------
    def _config(self):
        if self._cfg is _UNREAD:
            from .. import env as _env

            d = _env.get_str("MXNET_TRACE_DIR")
            self._cfg = None if not d else (
                d, max(int(_env.get_int("MXNET_TRACE_STEPS") or 1), 1))
        return self._cfg

    def enabled(self) -> bool:
        if self._done:
            return False
        return self._config() is not None

    @contextlib.contextmanager
    def step_window(self, workload: str, k: int = 1):
        """Bracket ONE dispatch.  Yields None when the tracer is off
        (the common path: one attribute read), else a window handle
        whose ``.block(arrays)`` the caller invokes on the dispatch
        outputs so device work lands inside the trace."""
        cfg = None if self._done else self._config()
        if cfg is None:
            yield None
            return
        trace_dir, n_steps = cfg
        with self._lock:
            if self._done:
                cfg = None
            else:
                self._dispatches += 1
                warming = self._dispatches <= WARMUP_DISPATCHES
                if not warming and not self._tracing:
                    try:
                        os.makedirs(trace_dir, exist_ok=True)
                        start_device_trace(trace_dir)
                        self._tracing = True
                        self._workload = workload
                        self._t_capture0 = time.monotonic()
                        _log.info(
                            "traceview: recording %d %s window(s) -> %s",
                            n_steps, workload, trace_dir)
                    except Exception as exc:
                        _log.warning("traceview: start_trace failed "
                                     "(%r) — capture disabled", exc)
                        self._done = True
                        cfg = None
        if cfg is None or not self._tracing:
            yield None
            return
        win = _Window(self, self._recorded, max(int(k), 1))
        try:
            with annotation("mxnet:step:%d:k=%d"
                            % (win.index, win.k)):
                yield win
        finally:
            self._on_window_done(trace_dir, n_steps)

    def _on_window_done(self, trace_dir: str, n_steps: int) -> None:
        with self._lock:
            if self._done or not self._tracing:
                return
            self._recorded += 1
            if self._recorded < n_steps:
                return
            self._done = True
            self._tracing = False
        cost = None
        try:
            stop_device_trace()
            if self._t_capture0 is not None:
                cost = time.monotonic() - self._t_capture0
        except Exception as exc:
            _log.warning("traceview: stop_trace failed: %r", exc)
            return
        try:
            self._ingest(trace_dir, cost)
        except Exception as exc:
            _log.warning("traceview: trace ingest failed: %r", exc)

    def _ingest(self, trace_dir: str, capture_cost_s) -> None:
        from .. import diagnostics as _diag
        from .. import profiler as _profiler
        from . import parse as _parse

        trace_path = _parse.find_trace_file(trace_dir)
        if trace_path is None:
            _log.warning("traceview: no trace file under %r", trace_dir)
            return
        trace = _parse.load_trace(trace_path)
        plan = _diag.bucket_plan()
        try:
            _hdr, entries = _diag.recorder.snapshot()
        except Exception:
            entries = []
        # xplane sidecar: mxbkt<i> scope metadata — exact bucket
        # identity for the collectives (parse.load_op_index)
        op_index = None
        try:
            xplane = _parse.find_xplane_file(trace_path)
            if xplane:
                op_index = _parse.load_op_index(xplane)
        except Exception as exc:
            _log.warning("traceview: xplane sidecar unreadable (%r) — "
                         "falling back to issue-order bucket map", exc)
        summary = _parse.attribute(trace, plan_meta=plan,
                                   flight_entries=entries,
                                   workload=self._workload,
                                   op_index=op_index)
        rank, num_workers = _profiler._dist_info()
        summary["rank"] = rank
        summary["num_workers"] = num_workers
        summary["capture"] = {
            "trace_dir": trace_dir, "trace_path": trace_path,
            "steps_recorded": self._recorded,
            "warmup_skipped": WARMUP_DISPATCHES,
            "capture_cost_s": capture_cost_s,
            "captured_at": time.time(),
        }
        path = os.path.join(trace_dir,
                            "traceview_summary_rank%d.json" % rank)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(summary, f, indent=1)
        os.replace(tmp, path)
        self._summary = summary
        self._summary_path = path
        _diag.feed_phase_seconds(
            {p: v.get("per_step_s") or []
             for p, v in summary["phases"].items()})
        _log.info("traceview: attributed %d device event(s) over %d "
                  "step(s) -> %s", summary.get("n_device_events", 0),
                  summary["steps"]["n"], path)


class _Window:
    def __init__(self, tracer: StepTracer, index: int, k: int):
        self.tracer = tracer
        self.index = index
        self.k = k

    def block(self, arrays: Any) -> None:
        """Block on the dispatch outputs INSIDE the annotation window
        so the device ops complete before the trace stops."""
        try:
            import jax

            jax.block_until_ready(arrays)
        except Exception:
            pass


_tracer = StepTracer()


def step_window(workload: str, k: int = 1):
    """Module-level dispatch hook (dp.py / transformer / bulk fit /
    serving call this): ``with step_window("FusedTrainStep", k=2) as w:
    ... w and w.block(out)``."""
    return _tracer.step_window(workload, k=k)


def enabled() -> bool:
    return _tracer.enabled()


def last_summary() -> Optional[dict]:
    """The attributed summary of this process's capture (None until a
    capture completed) — profiler.summary()'s phase table reads it."""
    return _tracer._summary


def last_summary_path() -> Optional[str]:
    return _tracer._summary_path


def reset() -> None:
    """Re-arm the single-shot tracer (tests)."""
    global _tracer
    _tracer = StepTracer()
