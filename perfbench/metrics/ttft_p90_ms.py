"""90th percentile, over all requests DUE in the window, of first token
minus the time the request was due; a shed or failed request counts
with the whole wait.  Not an end-to-end metric: at some 70 requests a
window it differs by up to 16 % between two runs of one seed (my chip
runs, PR 25), more than any bound may be.  Under continuous batching a
request's prefill stalls every rider, so it moves the token-gap tail."""


def read(ctx):
    return ctx["counters"].get("ttft_p90_ms")
