"""Device milliseconds a finished step in an expert layer's routing (the ``moe_route`` scope: scores, top-k, the sort, the gather into expert order, the weighted combine, the bias rule), forward, recomputed
and backward (``perfbench/program_trace_moe.py``)."""
from perfbench import program_trace_moe


def read(ctx):
    return program_trace_moe.name_ms(ctx, "moe_route")
