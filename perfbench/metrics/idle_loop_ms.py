"""Device-idle milliseconds a decode tick under the own time of the
server's generation worker (``mx.serve.loop``: the poll, the routing,
the gauges, the breaker and canary accounting;
``perfbench/program_trace_engine.py``)."""
from perfbench import program_trace_engine


def read(ctx):
    return program_trace_engine.idle_ms(ctx, "loop")
