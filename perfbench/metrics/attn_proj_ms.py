"""Device milliseconds a finished step in the qkv and output projections
and rotary (the ``attn_proj`` scope).  Every instant of busy time goes
to one class (``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "attn_proj")
