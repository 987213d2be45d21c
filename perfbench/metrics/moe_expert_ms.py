"""Device milliseconds a finished step in the grouped products over the held experts (the ``moe_expert`` scope), forward, recomputed
and backward (``perfbench/program_trace_moe.py``)."""
from perfbench import program_trace_moe


def read(ctx):
    return program_trace_moe.name_ms(ctx, "moe_expert")
