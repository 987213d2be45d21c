"""Device-idle milliseconds a decode tick under the own time of the
engine's ``mx.tick`` and ``mx.prefill`` spans: the dispatch of the
compiled call, with the chip at the least lag behind the host that
causality allows, so the least the trace can give the dispatch
(``perfbench/program_trace_engine.py``)."""
from perfbench import program_trace_engine


def read(ctx):
    return program_trace_engine.idle_ms(ctx, "launch")
