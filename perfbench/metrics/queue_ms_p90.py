"""90th percentile of the time a request waited for a slot, from the
program's own request traces (``serving/reqtrace.py``, the ``queue``
phase), read after the window."""
from perfbench.loadgen import percentile


def read(ctx):
    waits = ctx["counters"].get("queue_ms")
    return percentile(waits, 0.90) if waits else None
