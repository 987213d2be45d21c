"""Host time of one call into the train step, up to its return (before
the block): what a step costs the host to enqueue.  Mean over the
window, from the harness's own ``bench.step`` spans."""


def read(ctx):
    calls = ctx["spans"].durations("bench.step")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
