"""Host milliseconds of the call into the compiled step alone: mean of
the program's ``mx.step.launch`` spans inside the window."""
from perfbench import program_trace


def read(ctx):
    return program_trace.mean_span_ms(ctx, "mx.step.launch")
