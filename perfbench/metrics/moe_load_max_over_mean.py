"""The busiest held expert's assignments over the mean held expert's,
the mean over the window's steps and expert layers (the step's own
counter, read after the window): 1 is an even load."""


def read(ctx):
    return ctx["counters"].get("moe_load_max_over_mean")
