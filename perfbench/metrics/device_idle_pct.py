"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    busy = ctx["busy"]
    if busy is None:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
