"""mxnet_tpu.traceview — measured device timeline.

The reference profiler attributed real engine-operator device time per
stream (``src/profiler/profiler.cc``); this package is that layer for
the rebuilt stack: capture.py is the ONE sanctioned ``jax.profiler``
wrapper (env-armed by ``MXNET_TRACE_DIR`` / ``MXNET_TRACE_STEPS``),
parse.py the jax-free walker that classifies device ops into step
phases (H2D / forward / backward / per-bucket reduce / optimizer /
D2H) and computes MEASURED per-bucket collective occupancy and
compute/comm overlap.  Consumers: ``autotune.timing.from_trace``,
``tools/merge_traces.py --health`` phase-skew,
``profiler.summary()``'s phase table.

``python -m mxnet_tpu.traceview --self-test`` replays the committed
miniature trace fixture through the walker against golden attribution.
"""
from .capture import (annotation, enabled, last_summary,  # noqa: F401
                      last_summary_path, reset, start_device_trace,
                      step_annotation, step_window, stop_device_trace)
from .scopes import (parse_hlo_scopes, program_scopes,  # noqa: F401
                     scope_path)
from .parse import (SUMMARY_FORMAT, SUMMARY_VERSION,  # noqa: F401
                    attribute, classify_op, find_trace_file,
                    is_traceview_summary, load_trace)

__all__ = [
    "SUMMARY_FORMAT", "SUMMARY_VERSION", "attribute", "classify_op",
    "find_trace_file", "is_traceview_summary", "load_trace",
    "annotation", "enabled", "last_summary", "last_summary_path",
    "reset", "start_device_trace", "step_annotation", "step_window",
    "stop_device_trace", "parse_hlo_scopes", "program_scopes",
    "scope_path",
]
