"""Device milliseconds a finished step in the tied head's logits and the
loss (the ``head_loss`` scope).  Every instant of busy time goes to one
class (``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "head_loss")
