"""Device milliseconds a decode execution under the ``attn`` scope of a
latent block: the gather of the cached latent rows through the block
tables and the absorbed scores, softmax and sum over them, all layers
(``perfbench/program_trace_serve.py``; ``decode_fn`` programs only)."""
from perfbench import program_trace_serve


def read(ctx):
    return program_trace_serve.name_ms(ctx, "attn")
