"""The whole serving loop's share of the chips' bf16 peak: forward
FLOPs of every prompt token prefilled and every token decoded in the
traced window over window x chips x peak."""


def read(ctx):
    busy, peaks = ctx["busy"], ctx["peaks"]
    if busy is None or peaks is None:
        return None
    c = ctx["counters"]
    done = c["prefill_flops"] + c["decode_flops"]
    return 100.0 * done / (busy["window_s"] * ctx["chips"]
                           * peaks["bf16_flops_per_s"])
