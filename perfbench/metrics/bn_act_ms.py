"""Device milliseconds a finished step in ``BatchNorm``, ``Activation``,
``elemwise_add`` and ``Pooling``: the elementwise and reduce passes XLA
fuses into one another.  Every instant of busy time goes to one class
(``perfbench/program_trace.py``)."""
from perfbench import program_trace


def read(ctx):
    return program_trace.class_ms(ctx, "bn_act")
