"""Device milliseconds a decode execution under ``moe_expert``: the
grouped products over the held experts, all expert layers
(``perfbench/program_trace_serve.py``; ``decode_fn`` programs only)."""
from perfbench import program_trace_serve


def read(ctx):
    return program_trace_serve.name_ms(ctx, "moe_expert")
