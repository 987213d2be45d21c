"""Device-idle milliseconds a decode tick while the engine's thread
prepared a compiled call (``mx.engine.prepare``: the reap, the
admission's host arrays, the cache's extension, the plan cell, tokens,
positions and tables; ``perfbench/program_trace_engine.py``)."""
from perfbench import program_trace_engine


def read(ctx):
    return program_trace_engine.idle_ms(ctx, "prepare")
