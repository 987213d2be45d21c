"""Share of the decode slots that held a live sequence, weighted by
tick time: sum of tick time x ``live`` over sum of tick time x
``slots``, from the engine's ``mx.tick`` spans inside the window.  No
cell reports it today (``PERF.md`` §7 row 2)."""
from perfbench import program_trace


def read(ctx):
    ticks = program_trace.spans_in_window(ctx, "mx.tick")
    full = sum((s.t1 - s.t0) * s.args["slots"] for s in ticks or ())
    if not full:
        return None
    return 100.0 * sum((s.t1 - s.t0) * s.args["live"] for s in ticks) / full
