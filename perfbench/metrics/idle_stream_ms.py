"""Device-idle milliseconds a decode tick while the engine's thread
streamed a call's tokens (``mx.engine.stream``: the callers'
``on_token``, the retirements, the allocator's gauges;
``perfbench/program_trace_engine.py``)."""
from perfbench import program_trace_engine


def read(ctx):
    return program_trace_engine.idle_ms(ctx, "stream")
