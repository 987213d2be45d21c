"""Device milliseconds a decode execution under ``moe_route``: scores,
top-k, the sort by expert, the gather into the experts' buffer and the
weighted combine, all expert layers
(``perfbench/program_trace_serve.py``; ``decode_fn`` programs only)."""
from perfbench import program_trace_serve


def read(ctx):
    return program_trace_serve.name_ms(ctx, "moe_route")
