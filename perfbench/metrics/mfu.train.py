"""The whole training step's share of the chips' bf16 peak: forward and
backward FLOPs of the steps finished in the traced window (from the
configuration's shapes, two per multiply-add, nothing recomputed
counted) over window x chips x peak."""


def read(ctx):
    busy, peaks = ctx["busy"], ctx["peaks"]
    if busy is None or peaks is None:
        return None
    done = ctx["counters"]["flops_per_step"] * ctx["counters"]["steps"]
    return 100.0 * done / (busy["window_s"] * ctx["chips"]
                           * peaks["bf16_flops_per_s"])
