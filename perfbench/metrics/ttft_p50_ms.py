"""Median, over all requests DUE in the window, of first token minus
the time the request was due; a shed or failed request counts with the
whole wait.  It is no end-to-end metric: over some 37 requests a window
it spread by 5.0 and 6.3 % in two sets of six runs (my chip runs, PR
34), which no bound of at most 10 % can hold."""


def read(ctx):
    return ctx["counters"].get("ttft_p50_ms")
