"""Device milliseconds a finished step in the MLP (the ``mlp`` scope).
Every instant of busy time goes to one class
(``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "mlp")
