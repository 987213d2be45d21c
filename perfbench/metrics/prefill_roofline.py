"""Prefill programs' share of their roofline: the least time the chip
could take for the prompt tokens prefilled in the traced window (their
forward FLOPs over the bf16 peak, or the weights read once a prefill
over the HBM peak, whichever is larger) over the device time of the
``prefill_fn`` programs in the trace.  FLOPs bound it from about 240
prompt tokens a prefill upward."""
from perfbench import flops, trace_reduce


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    if peaks is None or trace is None:
        return None
    ran = trace_reduce.module_seconds(trace, r"prefill_fn")
    c = ctx["counters"]
    if ran is None or not c["prefill_requests"]:
        return None
    weights = flops.transformer_params(ctx["config"]) * 2.0
    least = sum(max(f / peaks["bf16_flops_per_s"],
                    weights / peaks["hbm_bytes_per_s"])
                for f in c["prefill_flops_each"])
    return 100.0 * least / ran["seconds"]
