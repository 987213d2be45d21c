"""Device milliseconds a finished step in the attention core alone (the
``attn`` scope: flash attention's scans, forward, recomputed and
backward).  Every instant of busy time goes to one class
(``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "attn")
