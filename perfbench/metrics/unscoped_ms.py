"""Device milliseconds a finished step in operations with no vocabulary
name on their scope path: what the program's names do not reach.  Every
instant of busy time goes to one class
(``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "unscoped")
