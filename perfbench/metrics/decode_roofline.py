"""Decode ticks' share of their roofline: the least time the chip could
take for the ticks of the traced window (every weight read once a tick
plus the keys and values of the tokens live behind each token emitted,
over the HBM peak; or the FLOPs over the bf16 peak if larger) over the
device time of the ``decode_fn`` programs in the trace.  The work is
counted from the cell's shapes and the tokens served, never from what
the implementation reads.  Bytes bound it at these batch sizes."""
from perfbench import flops, trace_reduce


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    if peaks is None or trace is None:
        return None
    ran = trace_reduce.module_seconds(trace, r"decode_fn")
    c = ctx["counters"]
    if ran is None or not c["decode_tokens"]:
        return None
    cfg = ctx["config"]
    moved = ran["runs"] * flops.transformer_params(cfg) * 2.0 \
        + c["decode_kv_token_reads"] * flops.kv_bytes_per_token(cfg)
    least = max(moved / peaks["hbm_bytes_per_s"],
                c["decode_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / ran["seconds"]
