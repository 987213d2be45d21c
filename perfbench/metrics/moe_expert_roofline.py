"""The grouped expert products' share of their roofline: the least time
the chip could take for the assignments the held experts actually
served in the window (the step's own counter: three products a
direction, forward and twice backward, ``perfbench/flops_moe.py``),
the larger of operations over the bf16 peak and bytes over the memory
peak, over the device time under ``moe_expert``.  The forward
recomputed under ``block`` remat is in the time and not in the work,
so the share reads low."""
from perfbench import flops_moe, program_trace_moe


def read(ctx):
    ms, peaks = program_trace_moe.name_ms(ctx, "moe_expert"), ctx["peaks"]
    served = ctx["counters"].get("moe_assignments_here")
    if ms is None or peaks is None or served is None:
        return None
    steps, cfg = ctx["counters"]["steps"], ctx["config"]
    least = max(flops_moe.expert_step_flops(cfg, served)
                / peaks["bf16_flops_per_s"],
                flops_moe.expert_step_bytes(cfg, served, steps)
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3 * steps)
