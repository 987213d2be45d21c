"""Device milliseconds a finished step in the residual streams' maps, Sinkhorn and mixing (the ``mhc`` scope), forward, recomputed
and backward (``perfbench/program_trace_moe.py``)."""
from perfbench import program_trace_moe


def read(ctx):
    return program_trace_moe.name_ms(ctx, "mhc")
