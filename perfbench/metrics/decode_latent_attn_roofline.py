"""Decode's latent attention's share of its roofline: the least time
the chip could take for the window's ticks over the device time under
the ``attn`` scope of the ``decode_fn`` programs.  The WORK is the
driver's count (its ``_work``, ``perfbench/flops_sarvam.py``): the live
latent rows read once a tick a layer (``latent_attn_bytes``) over the
memory peak, or the absorbed scores' and sums' operations
(``latent_attn_flops``) over the bf16 peak if larger.  The gather's
write and the second read of the gathered rows are in the time and not
in the work, so the share reads low."""
from perfbench import program_trace_serve


def read(ctx):
    table, peaks = program_trace_serve.decode_scope_ms(ctx), ctx["peaks"]
    c = ctx["counters"]
    if table is None or peaks is None or not table["attn"] \
            or "latent_attn_bytes" not in c:
        return None
    least = max(c["latent_attn_bytes"] / peaks["hbm_bytes_per_s"],
                c["latent_attn_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / (table["attn"] * 1e-3 * table["runs"])
