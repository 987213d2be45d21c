"""90th percentile, over all requests DUE in the window, of first token
minus the time the request was due; a shed or failed request counts
with the whole wait.  The serving cell is out of ``BENCHMARK.json``
(``PERF.md`` §7): at some 70 requests a window this tail differed by up
to 16 % between two runs of one seed (my chip runs, PR 25), so the cell
that comes back needs more requests a window, or a median beside it,
to carry a time to first token end to end."""


def read(ctx):
    return ctx["counters"].get("ttft_p90_ms")
