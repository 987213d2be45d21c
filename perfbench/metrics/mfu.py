"""The whole step's (training) or the whole serving loop's share of the
chips' bf16 peak: the FLOPs that the work finished in the traced window
needs (``flops_done``, which the driver counts from the configuration's
shapes: two per multiply-add, nothing recomputed counted) over window x
chips x peak."""


def read(ctx):
    busy, peaks = ctx["busy"], ctx["peaks"]
    if busy is None or peaks is None:
        return None
    return 100.0 * ctx["counters"]["flops_done"] / (
        busy["window_s"] * ctx["chips"] * peaks["bf16_flops_per_s"])
