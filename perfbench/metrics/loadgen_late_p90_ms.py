"""How late the benchmark's own generator sent its requests (send time
minus due time), 90th percentile: a starved generator must not read as
a fast server."""
from perfbench.loadgen import percentile


def read(ctx):
    late = ctx["counters"].get("late_ms")
    return percentile(late, 0.90) if late else None
