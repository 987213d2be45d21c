"""Device-idle milliseconds a decode tick while the engine's thread
read a compiled call's logits back and took their argmax
(``mx.tick.readback``, ``mx.prefill.readback``; it waits there through
the gaps inside the call's own run too), with the chip at the least lag
behind the host that causality allows, so the most the trace can give
the readback (``perfbench/program_trace_engine.py``)."""
from perfbench import program_trace_engine


def read(ctx):
    return program_trace_engine.idle_ms(ctx, "readback")
