"""Device milliseconds a finished step in the multi-token modules, whole (the ``mtp`` scope: their attention, experts, streams, head and loss, so it overlaps the other readers), forward, recomputed
and backward (``perfbench/program_trace_moe.py``)."""
from perfbench import program_trace_moe


def read(ctx):
    return program_trace_moe.name_ms(ctx, "mtp")
