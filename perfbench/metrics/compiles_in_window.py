"""Compilations the program's instrumented steps made inside the
measured window: its ``mx.compile`` spans that start there.  There
should be none."""
from perfbench import program_trace


def read(ctx):
    return program_trace.count_spans(ctx, "mx.compile")
