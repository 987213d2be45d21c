"""Share of the held experts that a decode tick reached with at least
one live rider's assignment, a layer, the mean over the window's ticks
(the compiled steps' own counter, read after the window): what a tick
has to read of the experts' matrices."""


def read(ctx):
    return ctx["counters"].get("moe_experts_reached_pct")
