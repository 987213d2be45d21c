"""Share of the decode slots that held a live sequence, weighted by
tick time: sum of tick time x ``live`` over sum of tick time x
``slots``, from the engine's ``mx.tick`` spans that start inside the
window.  A share of slots, counted by the program: a run without a chip
reads it too."""
from perfbench import program_trace


def read(ctx):
    spans = program_trace.ring_spans(ctx)
    if spans is None:
        return None
    return program_trace.slot_occupancy_pct(
        spans, *program_trace.window_on_host(ctx))
