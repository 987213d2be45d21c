"""Decode's grouped expert products' share of their roofline: the least
time the chip could take over the device time under ``moe_expert`` of
the ``decode_fn`` programs.  The WORK is the driver's count (its
``_work``, ``perfbench/flops_sarvam.py``) from the program's own
counter: three matrices of every held expert that a tick REACHED, a
layer (``moe_expert_bytes``), over the memory peak, or the served
assignments' operations (``moe_expert_flops``) over the bf16 peak if
larger.  An expert that only an empty slot's padding row reached is
read and not counted."""
from perfbench import program_trace_serve


def read(ctx):
    table, peaks = program_trace_serve.decode_scope_ms(ctx), ctx["peaks"]
    c = ctx["counters"]
    if table is None or peaks is None or not table["moe_expert"] \
            or "moe_expert_bytes" not in c:
        return None
    least = max(c["moe_expert_bytes"] / peaks["hbm_bytes_per_s"],
                c["moe_expert_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / (table["moe_expert"] * 1e-3 * table["runs"])
