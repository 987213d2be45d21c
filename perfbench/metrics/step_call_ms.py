"""Host milliseconds of one call into the train step by the program's
own reckoning: mean of its ``mx.step`` spans inside the window (feed,
launch and bookkeeping; what ``dispatch_ms`` measures from outside)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.mean_span_ms(ctx, "mx.step")
